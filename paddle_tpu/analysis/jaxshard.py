"""jaxshard: whole-program static SPMD/sharding analyzer.

jaxcost charges collective bytes only where the program says `psum`;
under GSPMD most collectives are IMPLICIT — XLA inserts them wherever
the sharding it propagated for an operand disagrees with what an
equation needs. This module makes those insertions visible *before*
compilation: an abstract interpreter over jaxprs that propagates
NamedSharding / PartitionSpec annotations (pjit in/out shardings,
`with_sharding_constraint` sites, shard_map specs) through every
equation, inferring each intermediate's sharding and flagging where XLA
must reshard. Lineage: "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" (PAPERS.md) — sharding decisions are
derived and checked from the program, not hand-tuned.

Per program the analyzer emits:

- resharding edges, with wire bytes charged PER MESH AXIS (the byte
  model extends jaxcost's collective table — see below);
- accidental full replication: a transition that rematerializes a
  >= 1 MiB tensor fully replicated on every device;
- donation defeated by sharding: a donated input whose aval-matched
  output either carries a different final sharding (aliasing is
  layout-impossible) or is produced through a resharding edge (XLA
  materializes a gathered copy before writing the aliased buffer);
- per-device peak live bytes: the liveness peak with every buffer
  divided by its true shard factor, checked against the jaxplan HBM
  envelope.

Byte model (deterministic; global-payload semantics, consistent with
jaxcost's per-equation table so the two artifacts cross-check):

    implicit psum (partial resolution)   2 x global result bytes / axis
    implicit all_gather (unshard a dim)  1 x global result bytes / axis
    implicit reshard (axis moves dims)   1 x global result bytes / axis
    replicated -> sharded (slice)        0   (each device keeps a slice)
    explicit collective in shard_map     exactly jaxcost's charge
                                         (2x-in / out / in), split over
                                         the equation's named axes

Partial sums are resolved EAGERLY: a dot_general contracting a sharded
dimension charges its psum at the dot itself (XLA may defer the reduce,
but the dot is where the partial value is born, and eager resolution
keeps the model one-pass deterministic). Mesh axes of size 1 are
dropped when specs are normalized, so `build_mesh(dp=4)` meshes do not
produce phantom edges on the five size-1 axes.

The registry (>= 8 programs: the fsdp x tp training step, dp training,
the ring/ulysses/psum_tree explicit collectives shared with jaxcost,
and the TP serving decode sub-programs) commits its reports to
`shardplan.json` with the same write/check/tolerance discipline as
jaxcost_budget.json / jaxplan.json: 5% byte tolerance, structural
drift exact, full coverage both directions, and every finding must
carry a triage reason (suppression) before the plan can be written.
CLI: tools/jaxshard.py (`--plan write|check`, exit 0/1/2).
"""
from __future__ import annotations

# ptlint: disable-file=PT-T004  registry builders construct jax.jit
# wrappers for TRACING only (analyze_jit needs the pjit equation's
# in/out shardings); each builds at most once per analysis run behind
# lru-cached setup and nothing here is a serving/training hot path

import functools
import json
import os
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .liveness import aval_bytes, peak_live_bytes, var_bytes

__all__ = [
    "ShardReport", "ReshardEdge", "ShardFinding",
    "analyze_jit", "compute_reports", "registry_names",
    "DEFAULT_PLAN_PATH", "DEFAULT_TOLERANCE", "PLAN_VERSION",
    "write_plan", "check_plan", "diff_plans", "load_plan",
    "crosscheck_with_budget", "committed_shard_factors",
]

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_PLAN_PATH = os.path.join(_REPO, "shardplan.json")
PLAN_VERSION = 1
DEFAULT_TOLERANCE = 0.05

#: implicit edges below this wire-byte total never become findings
#: (scalar loss psums etc. are charged but not triaged)
IMPLICIT_MIN_BYTES = 1024
#: "accidental full replication" findings start here
REPLICATION_MIN_BYTES = 1 << 20

# jaxcost's collective byte table (kept in sync by the cross-artifact
# check in tools/jaxcost.py): all-reduce family 2x input, gathers their
# output, permutes / all-to-all / scatters their input.
_COMM_TWICE_IN = frozenset({"psum", "psum2", "pmax", "pmin", "pmax2",
                            "pmin2", "pmean"})
_COMM_OUT = frozenset({"all_gather", "all_gather_invariant"})
_COMM_IN = frozenset({"reduce_scatter", "psum_scatter", "ppermute",
                      "pshuffle", "all_to_all"})

#: equations that run a sub-jaxpr transparently (same operand order)
_TRANSPARENT_CALLS = frozenset({
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "remat", "checkpoint", "closed_call", "core_call", "custom_lin",
})


# ------------------------------------------------------------------ specs
#
# A normalized spec is a tuple with one entry per array dim:
#   None            unsharded on that dim
#   ("tp",)         sharded over mesh axis tp
#   ("dp", "sh")    sharded over two axes (major to minor)
# Axes whose mesh size is 1 are dropped at normalization time.

def _replicated(ndim: int) -> tuple:
    return (None,) * ndim


def _norm_entry(entry, sizes: Dict[str, int]):
    if entry is None:
        return None
    names = entry if isinstance(entry, (tuple, list)) else (entry,)
    kept = tuple(str(a) for a in names if sizes.get(str(a), 1) > 1)
    return kept or None


def _spec_of_pspec(pspec, ndim: int, sizes: Dict[str, int],
                   unconstrained=frozenset()) -> tuple:
    """PartitionSpec -> normalized per-dim tuple. `unconstrained` dims
    come out as None (caller keeps the incoming sharding there)."""
    entries = tuple(pspec) + (None,) * (ndim - len(tuple(pspec)))
    out = []
    for d, e in enumerate(entries[:ndim]):
        if d in unconstrained or _is_unconstrained(e):
            out.append(None)
        else:
            out.append(_norm_entry(e, sizes))
    return tuple(out)


def _is_unconstrained(entry) -> bool:
    from jax.sharding import PartitionSpec as P
    return entry is P.UNCONSTRAINED


def _spec_str(spec) -> str:
    def one(e):
        return "-" if not e else "+".join(e)
    return "[" + ",".join(one(e) for e in spec) + "]"


def _spec_axes(spec) -> Tuple[str, ...]:
    out = []
    for e in spec:
        for a in e or ():
            if a not in out:
                out.append(a)
    return tuple(out)


def _shard_factor(spec, sizes: Dict[str, int]) -> int:
    f = 1
    for a in _spec_axes(spec):
        f *= sizes.get(a, 1)
    return f


def _mesh_sizes(mesh) -> Dict[str, int]:
    return {str(k): int(v) for k, v in dict(mesh.shape).items()
            if int(v) > 1}


# ------------------------------------------------------------------ report
@dataclass(frozen=True)
class ReshardEdge:
    """One place GSPMD must move data. `axes -> bytes` is the per-axis
    wire charge (already multiplied by loop trip counts)."""
    path: str
    primitive: str
    kind: str                      # psum | all_gather | reshard
    axis_bytes: Dict[str, int]
    tensor_bytes: int
    src: str
    dst: str

    def to_dict(self) -> dict:
        return {"path": self.path, "primitive": self.primitive,
                "kind": self.kind, "axis_bytes": dict(self.axis_bytes),
                "tensor_bytes": self.tensor_bytes,
                "src": self.src, "dst": self.dst}


@dataclass
class ShardFinding:
    """One triaged item. Aggregated implicit-collective groups,
    replication sites, donation defeats and envelope breaches all
    share this shape; `key` is the suppression key committed in
    shardplan.json."""
    key: str
    kind: str          # implicit | replication | donation | envelope
    message: str
    nbytes: int = 0
    count: int = 1
    example: str = ""
    suppressed: Optional[str] = None

    def to_dict(self) -> dict:
        return {"key": self.key, "kind": self.kind,
                "message": self.message, "nbytes": self.nbytes,
                "count": self.count, "example": self.example,
                "suppressed": self.suppressed}

    def format(self) -> str:
        tag = "suppressed" if self.suppressed else "UNSUPPRESSED"
        return (f"  [{tag}] {self.key}: {self.message}"
                + (f"  # {self.suppressed}" if self.suppressed else ""))


@dataclass
class ShardReport:
    name: str
    mesh: Dict[str, int]
    edges: List[ReshardEdge] = field(default_factory=list)
    implicit_axis_bytes: Dict[str, int] = field(default_factory=dict)
    explicit_axis_bytes: Dict[str, int] = field(default_factory=dict)
    findings: List[ShardFinding] = field(default_factory=list)
    per_device_peak_bytes: int = 0
    peak_where: str = ""
    envelope_bytes: int = 0
    notes: List[str] = field(default_factory=list)

    @property
    def comm_bytes_total(self) -> int:
        return (sum(self.implicit_axis_bytes.values())
                + sum(self.explicit_axis_bytes.values()))

    def unsuppressed(self) -> List[ShardFinding]:
        return [f for f in self.findings if not f.suppressed]

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "mesh": dict(sorted(self.mesh.items())),
            "edge_count": len(self.edges),
            "implicit_axis_bytes": dict(
                sorted(self.implicit_axis_bytes.items())),
            "explicit_axis_bytes": dict(
                sorted(self.explicit_axis_bytes.items())),
            "comm_bytes_total": self.comm_bytes_total,
            "per_device_peak_bytes": self.per_device_peak_bytes,
            "peak_where": self.peak_where,
            "envelope_ok": self.per_device_peak_bytes
            <= self.envelope_bytes,
            "findings": {f.key: f.to_dict() for f in self.findings},
        }

    def format(self) -> str:
        lines = [f"{self.name}: mesh={self.mesh} "
                 f"edges={len(self.edges)} "
                 f"comm={self.comm_bytes_total:,}B "
                 f"per_device_peak={self.per_device_peak_bytes:,}B"]
        for ax in sorted(set(self.implicit_axis_bytes)
                         | set(self.explicit_axis_bytes)):
            lines.append(
                f"  axis {ax}: implicit "
                f"{self.implicit_axis_bytes.get(ax, 0):,}B + explicit "
                f"{self.explicit_axis_bytes.get(ax, 0):,}B")
        for f in self.findings:
            lines.append(f.format())
        for n in self.notes[:6]:
            lines.append(f"  note: {n}")
        return "\n".join(lines)


# ------------------------------------------------------------ interpreter
class _Acc:
    """One recording sink: edges + per-axis byte tallies. Probe passes
    (cond branches, scan fixpoint) run against a scratch sink so only
    the chosen/final pass charges the real one."""

    def __init__(self):
        self.edges: List[ReshardEdge] = []
        self.implicit: Dict[str, int] = {}
        self.explicit: Dict[str, int] = {}
        self.repl_sites: List[Tuple[str, str, int]] = []
        self.notes: List[str] = []

    def total(self) -> int:
        return sum(self.implicit.values()) + sum(self.explicit.values())


class _Interp:
    """Forward abstract interpretation of shardings over one program."""

    def __init__(self, name: str, sizes: Dict[str, int]):
        self.name = name
        self.sizes = sizes
        self.specs: Dict[object, tuple] = {}
        self.acc = _Acc()
        self.manual_depth = 0

    # -------------------------------------------------------- plumbing
    def read(self, atom) -> tuple:
        if _lit(atom):
            return _replicated(len(getattr(atom.aval, "shape", ())))
        got = self.specs.get(atom)
        if got is None:
            got = _replicated(len(atom.aval.shape))
        return got

    def write(self, var, spec) -> None:
        self.specs[var] = spec

    def note(self, msg: str) -> None:
        if msg not in self.acc.notes:
            self.acc.notes.append(msg)

    # ------------------------------------------------------- charging
    def _charge(self, kind: str, axes: Sequence[str], nbytes: int,
                mult: int, path: str, prim: str,
                src: tuple, dst: tuple) -> None:
        """One implicit resharding edge; psum charges 2x per axis."""
        per = 2 * nbytes if kind == "psum" else nbytes
        axis_bytes = {}
        for a in sorted(set(axes)):
            b = per * mult
            axis_bytes[a] = b
            self.acc.implicit[a] = self.acc.implicit.get(a, 0) + b
        if not axis_bytes:
            return
        self.acc.edges.append(ReshardEdge(
            path=path, primitive=prim, kind=kind, axis_bytes=axis_bytes,
            tensor_bytes=nbytes, src=_spec_str(src), dst=_spec_str(dst)))

    def _charge_explicit(self, eqn, path: str, mult: int) -> None:
        """Explicit collective: jaxcost's exact per-equation charge,
        attributed to the equation's named mesh axes."""
        name = eqn.primitive.name
        if name in _COMM_TWICE_IN:
            total = 2 * sum(var_bytes(v) for v in eqn.invars)
            axes = eqn.params.get("axes", ())
        elif name in _COMM_OUT:
            total = sum(var_bytes(v) for v in eqn.outvars)
            axes = (eqn.params.get("axis_name"),)
        else:
            total = sum(var_bytes(v) for v in eqn.invars)
            axes = (eqn.params.get("axis_name"),)
        flat = []
        for a in (axes or ()):
            if isinstance(a, (tuple, list)):
                flat.extend(a)
            elif a is not None:
                flat.append(a)
        named = sorted({str(a) for a in flat
                        if self.sizes.get(str(a), 1) > 1}) or ["?"]
        share = (total * mult) // len(named)
        for a in named:
            self.acc.explicit[a] = self.acc.explicit.get(a, 0) + share

    def transition(self, src: tuple, dst: tuple, aval, path: str,
                   prim: str, mult: int) -> None:
        """Charge whatever data movement turning `src` into `dst` costs
        (None = free slice). Records replication sites for the
        accidental-replication detector."""
        if src == dst:
            return
        nbytes = aval_bytes(aval)
        gathered, moved = [], []
        for s_e, d_e in zip(src, dst):
            s_set, d_set = set(s_e or ()), set(d_e or ())
            gathered.extend(sorted(s_set - d_set))
            if (d_set - s_set) and (s_set - d_set):
                moved.extend(sorted(s_set ^ d_set))
        if not gathered and not moved:
            return  # pure replicated->sharded: each device slices, free
        kind = "reshard" if moved else "all_gather"
        axes = sorted(set(gathered) | set(moved))
        self._charge(kind, axes, nbytes, mult, path, prim, src, dst)
        if (not any(dst) and any(src)
                and nbytes >= REPLICATION_MIN_BYTES):
            self.acc.repl_sites.append(
                (f"{prim}:{'+'.join(axes)}", path, nbytes))

    # ------------------------------------------------------------ run
    def run(self, jaxpr_like, in_specs: Sequence[tuple], path: str,
            mult: int = 1) -> List[tuple]:
        raw = getattr(jaxpr_like, "jaxpr", jaxpr_like)
        consts = getattr(raw, "constvars", ())
        for v in consts:
            self.write(v, _replicated(len(getattr(v.aval, "shape", ()))))
        for v, s in zip(raw.invars, in_specs):
            self.write(v, s)
        for i, eqn in enumerate(raw.eqns):
            self.eqn(eqn, f"{path}:{i}", mult)
        return [self.read(v) for v in raw.outvars]

    def _probe(self, fn) -> Tuple[int, object]:
        """Run `fn` against a scratch sink; return (bytes, result)."""
        saved, self.acc = self.acc, _Acc()
        try:
            out = fn()
            return self.acc.total(), out
        finally:
            self.acc = saved

    # ------------------------------------------------------- dispatch
    def eqn(self, eqn, path: str, mult: int) -> None:
        name = eqn.primitive.name
        handler = getattr(self, f"_h_{name}", None)
        if handler is not None:
            handler(eqn, path, mult)
            return
        if name in _COMM_TWICE_IN or name in _COMM_OUT \
                or name in _COMM_IN:
            self._charge_explicit(eqn, path, mult)
            # per-shard view: collectives return replicated-in-manual
            for v in eqn.outvars:
                self.write(v, _replicated(len(v.aval.shape)))
            return
        if name in _TRANSPARENT_CALLS:
            self._h_transparent(eqn, path, mult)
            return
        if name.startswith(("reduce_", "arg")) and "axes" in eqn.params:
            self._h_reduce(eqn, path, mult)
            return
        if name.startswith("cum"):
            self._h_cum(eqn, path, mult)
            return
        self._h_default(eqn, path, mult)

    # default: elementwise join over same-shaped operands
    def _h_default(self, eqn, path: str, mult: int) -> None:
        out0 = eqn.outvars[0]
        oshape = tuple(getattr(out0.aval, "shape", ()))
        mates = [(v, self.read(v)) for v in eqn.invars
                 if tuple(getattr(v.aval, "shape", ())) == oshape]
        if not mates:
            if any(any(self.read(v)) for v in eqn.invars):
                self.note(f"unmodeled primitive {eqn.primitive.name}: "
                          f"sharded operand treated as replicated")
            for v in eqn.outvars:
                self.write(v, _replicated(len(v.aval.shape)))
            return
        joined = list(_replicated(len(oshape)))
        for _, s in mates:
            for d, e in enumerate(s):
                if joined[d] is None and e is not None:
                    joined[d] = e
        joined = tuple(joined)
        for v, s in mates:
            if s != joined and any(s):
                # operand laid out differently from the join: GSPMD
                # reshards it (replicated operands slice for free)
                self.transition(s, joined, v.aval, path,
                                eqn.primitive.name, mult)
        for v in eqn.outvars:
            if tuple(getattr(v.aval, "shape", ())) == oshape:
                self.write(v, joined)
            else:
                self.write(v, _replicated(len(v.aval.shape)))

    # ------------------------------------------------- sharding markers
    def _h_sharding_constraint(self, eqn, path: str, mult: int) -> None:
        v = eqn.invars[0]
        ndim = len(v.aval.shape)
        src = self.read(v)
        sharding = eqn.params["sharding"]
        unc = frozenset(eqn.params.get("unconstrained_dims", ()) or ())
        tgt = _spec_of_pspec(getattr(sharding, "spec", ()), ndim,
                             self.sizes, unconstrained=unc)
        dst = tuple(src[d] if d in unc else tgt[d] for d in range(ndim))
        self.transition(src, dst, v.aval, path, "sharding_constraint",
                        mult)
        self.write(eqn.outvars[0], dst)

    def _h_jit(self, eqn, path: str, mult: int) -> None:
        inner = eqn.params["jaxpr"]
        in_sh = eqn.params.get("in_shardings",
                               (None,) * len(eqn.invars))
        out_sh = eqn.params.get("out_shardings",
                                (None,) * len(eqn.outvars))
        entry = []
        for i, v in enumerate(eqn.invars):
            spec = self.read(v)
            sh = in_sh[i] if i < len(in_sh) else None
            pspec = getattr(sh, "spec", None)
            if pspec is not None:
                tgt = _spec_of_pspec(pspec, len(v.aval.shape),
                                     self.sizes)
                self.transition(spec, tgt, v.aval, f"{path}/in{i}",
                                "pjit", mult)
                spec = tgt
            entry.append(spec)
        body = self.run(inner, entry, f"{path}/pjit", mult)
        for i, v in enumerate(eqn.outvars):
            spec = body[i] if i < len(body) else \
                _replicated(len(v.aval.shape))
            sh = out_sh[i] if i < len(out_sh) else None
            pspec = getattr(sh, "spec", None)
            if pspec is not None:
                tgt = _spec_of_pspec(pspec, len(v.aval.shape),
                                     self.sizes)
                self.transition(spec, tgt, v.aval, f"{path}/out{i}",
                                "pjit", mult)
                spec = tgt
            self.write(v, spec)

    def _h_shard_map(self, eqn, path: str, mult: int) -> None:
        body = eqn.params["jaxpr"]
        in_names = eqn.params.get("in_names", ())
        out_names = eqn.params.get("out_names", ())
        for v, names in zip(eqn.invars, in_names):
            expected = self._spec_of_names(names, len(v.aval.shape))
            self.transition(self.read(v), expected, v.aval,
                            f"{path}/shmap_in", "shard_map", mult)
        raw = getattr(body, "jaxpr", body)
        self.manual_depth += 1
        try:
            self.run(body,
                     [_replicated(len(iv.aval.shape))
                      for iv in raw.invars],
                     f"{path}/shard_map", mult)
        finally:
            self.manual_depth -= 1
        for v, names in zip(eqn.outvars, out_names):
            self.write(v, self._spec_of_names(names,
                                              len(v.aval.shape)))

    def _spec_of_names(self, names, ndim: int) -> tuple:
        out = [None] * ndim
        for d, axes in dict(names or {}).items():
            if int(d) < ndim:
                out[int(d)] = _norm_entry(tuple(axes), self.sizes)
        return tuple(out)

    # ------------------------------------------------------- contraction
    def _h_dot_general(self, eqn, path: str, mult: int) -> None:
        (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
        lhs, rhs = eqn.invars[0], eqn.invars[1]
        ls, rs = self.read(lhs), self.read(rhs)
        ln, rn = len(lhs.aval.shape), len(rhs.aval.shape)
        out = eqn.outvars[0]

        batch = []
        for i, j in zip(lhs_b, rhs_b):
            a, b = ls[i], rs[j]
            if a and b and a != b:
                # operands tile the shared batch dim differently:
                # reshard rhs onto lhs's layout
                fixed = tuple(a if d == j else rs[d] for d in range(rn))
                self.transition(rs, fixed, rhs.aval, path,
                                "dot_general", mult)
                b = a
            batch.append(a or b)
        lhs_free = [ls[d] for d in range(ln)
                    if d not in lhs_c and d not in lhs_b]
        rhs_free = [rs[d] for d in range(rn)
                    if d not in rhs_c and d not in rhs_b]
        spec = tuple(batch + lhs_free + rhs_free)

        partial_axes = set()
        for d in lhs_c:
            partial_axes.update(ls[d] or ())
        for d in rhs_c:
            partial_axes.update(rs[d] or ())
        partial_axes -= {a for e in spec for a in (e or ())}
        if partial_axes:
            # contracting a sharded dim leaves every device a partial
            # sum: resolve eagerly with the implicit all-reduce here
            self._charge("psum", sorted(partial_axes),
                         aval_bytes(out.aval), mult, path,
                         "dot_general", spec, spec)
        self.write(out, spec[:len(out.aval.shape)]
                   + _replicated(len(out.aval.shape) - len(spec)))

    def _h_reduce(self, eqn, path: str, mult: int) -> None:
        v = eqn.invars[0]
        src = self.read(v)
        axes = tuple(eqn.params.get("axes", ()))
        hit = set()
        for d in axes:
            hit.update(src[d] or ())
        out_spec = tuple(e for d, e in enumerate(src) if d not in axes)
        if hit:
            self._charge("psum", sorted(hit),
                         aval_bytes(eqn.outvars[0].aval), mult, path,
                         eqn.primitive.name, src, out_spec)
        for ov in eqn.outvars:
            self.write(ov, out_spec[:len(ov.aval.shape)]
                       + _replicated(len(ov.aval.shape)
                                     - len(out_spec)))

    def _h_cum(self, eqn, path: str, mult: int) -> None:
        v = eqn.invars[0]
        src = self.read(v)
        d = eqn.params.get("axis", 0)
        dst = tuple(None if i == d else e for i, e in enumerate(src))
        if src[d]:
            self.transition(src, dst, v.aval, path,
                            eqn.primitive.name, mult)
        self.write(eqn.outvars[0], dst)

    # ---------------------------------------------------- shape plumbing
    def _h_broadcast_in_dim(self, eqn, path: str, mult: int) -> None:
        v = eqn.invars[0]
        src = self.read(v)
        bdims = eqn.params["broadcast_dimensions"]
        oshape = eqn.params["shape"]
        out = [None] * len(oshape)
        for j, d in enumerate(bdims):
            if int(v.aval.shape[j]) == int(oshape[d]):
                out[d] = src[j]
        self.write(eqn.outvars[0], tuple(out))

    def _h_transpose(self, eqn, path: str, mult: int) -> None:
        src = self.read(eqn.invars[0])
        perm = eqn.params["permutation"]
        self.write(eqn.outvars[0], tuple(src[p] for p in perm))

    def _h_squeeze(self, eqn, path: str, mult: int) -> None:
        src = self.read(eqn.invars[0])
        drop = set(eqn.params["dimensions"])
        self.write(eqn.outvars[0],
                   tuple(e for d, e in enumerate(src) if d not in drop))

    def _h_expand_dims(self, eqn, path: str, mult: int) -> None:
        src = list(self.read(eqn.invars[0]))
        for d in sorted(eqn.params["dimensions"]):
            src.insert(d, None)
        self.write(eqn.outvars[0], tuple(src))

    def _h_reshape(self, eqn, path: str, mult: int) -> None:
        v = eqn.invars[0]
        src = self.read(v)
        in_shape = tuple(int(d) for d in v.aval.shape)
        out_shape = tuple(int(d) for d in eqn.params["new_sizes"])
        spec, lost = _map_reshape(in_shape, out_shape, src, self.sizes)
        if lost:
            dst = tuple(spec)
            self.transition(src, _strip_axes(src, lost), v.aval, path,
                            "reshape", mult)
        self.write(eqn.outvars[0], tuple(spec))

    def _h_rev(self, eqn, path: str, mult: int) -> None:
        self.write(eqn.outvars[0], self.read(eqn.invars[0]))

    def _h_convert_element_type(self, eqn, path, mult) -> None:
        self.write(eqn.outvars[0], self.read(eqn.invars[0]))

    def _h_slice(self, eqn, path: str, mult: int) -> None:
        v = eqn.invars[0]
        src = self.read(v)
        starts = eqn.params["start_indices"]
        limits = eqn.params["limit_indices"]
        self._sliced(eqn, src, [int(l) - int(s) for s, l
                                in zip(starts, limits)], path, mult)

    def _h_dynamic_slice(self, eqn, path: str, mult: int) -> None:
        src = self.read(eqn.invars[0])
        self._sliced(eqn, src, eqn.params["slice_sizes"], path, mult)

    def _sliced(self, eqn, src, out_sizes, path, mult) -> None:
        v = eqn.invars[0]
        dst = []
        for d, e in enumerate(src):
            full = int(out_sizes[d]) == int(v.aval.shape[d])
            dst.append(e if full else None)
        dst = tuple(dst)
        if any(s and not d for s, d in zip(src, dst)):
            self.transition(src, dst, v.aval, path,
                            eqn.primitive.name, mult)
        self.write(eqn.outvars[0], dst)

    def _h_dynamic_update_slice(self, eqn, path, mult) -> None:
        op = eqn.invars[0]
        spec = self.read(op)
        upd = eqn.invars[1]
        us = self.read(upd)
        if any(us) and us[:len(spec)] != spec:
            self.transition(us, _replicated(len(us)), upd.aval, path,
                            "dynamic_update_slice", mult)
        self.write(eqn.outvars[0], spec)

    def _h_concatenate(self, eqn, path: str, mult: int) -> None:
        dim = eqn.params["dimension"]
        out = eqn.outvars[0]
        joined = list(_replicated(len(out.aval.shape)))
        for v in eqn.invars:
            s = self.read(v)
            if s[dim]:
                # concatenating along a sharded dim: gather first
                dst = tuple(None if d == dim else e
                            for d, e in enumerate(s))
                self.transition(s, dst, v.aval, path, "concatenate",
                                mult)
                s = dst
            for d, e in enumerate(s):
                if d != dim and joined[d] is None and e is not None:
                    joined[d] = e
        self.write(out, tuple(joined))

    def _h_pad(self, eqn, path: str, mult: int) -> None:
        self.write(eqn.outvars[0], self.read(eqn.invars[0]))

    def _h_iota(self, eqn, path: str, mult: int) -> None:
        self.write(eqn.outvars[0],
                   _replicated(len(eqn.outvars[0].aval.shape)))

    def _h_gather(self, eqn, path: str, mult: int) -> None:
        op, idx = eqn.invars[0], eqn.invars[1]
        os, xs = self.read(op), self.read(idx)
        dn = eqn.params["dimension_numbers"]
        out = eqn.outvars[0]
        out_ndim = len(out.aval.shape)
        offset = set(dn.offset_dims)
        # sharded lookup dims: GSPMD lowers a gather from a sharded
        # table as masked local lookup + psum of the dense result (the
        # vocab-parallel embedding pattern)
        lookup_axes = set()
        for d in set(dn.start_index_map) | set(dn.collapsed_slice_dims):
            lookup_axes.update(os[d] or ())
        # surviving operand dims feed the offset dims in order
        surviving = [d for d in range(len(os))
                     if d not in dn.collapsed_slice_dims]
        slice_sizes = eqn.params.get("slice_sizes", ())
        off_entries = []
        for d in surviving:
            full = (d < len(slice_sizes)
                    and int(slice_sizes[d]) == int(op.aval.shape[d]))
            off_entries.append(os[d] if full else None)
        batch_entries = [e for e in xs[:-1]] if len(xs) else []
        spec, oi, bi = [], 0, 0
        for d in range(out_ndim):
            if d in offset:
                spec.append(off_entries[oi] if oi < len(off_entries)
                            else None)
                oi += 1
            else:
                spec.append(batch_entries[bi]
                            if bi < len(batch_entries) else None)
                bi += 1
        if lookup_axes:
            self._charge("psum", sorted(lookup_axes),
                         aval_bytes(out.aval), mult, path, "gather",
                         os, tuple(spec))
            nbytes = aval_bytes(out.aval)
            if not any(spec) and nbytes >= REPLICATION_MIN_BYTES:
                self.acc.repl_sites.append(
                    (f"gather:{'+'.join(sorted(lookup_axes))}",
                     path, nbytes))
        self.write(out, tuple(spec))

    def _h_scatter(self, eqn, path: str, mult: int) -> None:
        self.write(eqn.outvars[0], self.read(eqn.invars[0]))

    _h_scatter_add = _h_scatter

    # ------------------------------------------------------ control flow
    def _h_scan(self, eqn, path: str, mult: int) -> None:
        p = eqn.params
        body = p["jaxpr"]
        raw = getattr(body, "jaxpr", body)
        n_c, n_carry = p["num_consts"], p["num_carry"]
        length = int(p.get("length", 1))
        consts = [self.read(v) for v in eqn.invars[:n_c]]
        carry = [self.read(v) for v in eqn.invars[n_c:n_c + n_carry]]
        xs = []
        for v in eqn.invars[n_c + n_carry:]:
            s = self.read(v)
            if s and s[0]:
                # scanning over a sharded leading dim: gather it
                dst = (None,) + tuple(s[1:])
                self.transition(s, dst, v.aval, path, "scan", mult)
                s = dst
            xs.append(tuple(s[1:]))
        # one scratch pass to a fixpoint on the carry layout, then the
        # recorded pass at trip-count multiplicity
        _, probe_out = self._probe(
            lambda: self.run(body, consts + carry + xs,
                             f"{path}/scan", mult))
        joined = [_meet(a, b) for a, b in
                  zip(carry, probe_out[:n_carry])]
        outs = self.run(body, consts + joined + xs, f"{path}/scan",
                        mult * max(length, 1))
        final_carry = [_meet(a, b) for a, b in
                       zip(joined, outs[:n_carry])]
        ys = [(None,) + tuple(s) for s in outs[n_carry:]]
        for v, s in zip(eqn.outvars, final_carry + ys):
            self.write(v, tuple(s)[:len(v.aval.shape)]
                       + _replicated(len(v.aval.shape) - len(s)))

    def _h_while(self, eqn, path: str, mult: int) -> None:
        p = eqn.params
        body = p["body_jaxpr"]
        n_b = p.get("body_nconsts", 0)
        n_cond = p.get("cond_nconsts", 0)
        carry = [self.read(v) for v in eqn.invars[n_cond + n_b:]]
        consts = [self.read(v)
                  for v in eqn.invars[n_cond:n_cond + n_b]]
        self.note("while body resharding charged once (trip count "
                  "unknown)")
        outs = self.run(body, consts + carry, f"{path}/while", mult)
        for v, a, b in zip(eqn.outvars, carry, outs):
            self.write(v, _meet(a, b))

    def _h_cond(self, eqn, path: str, mult: int) -> None:
        branches = eqn.params["branches"]
        operands = [self.read(v) for v in eqn.invars[1:]]
        # probe every branch; charge only the heaviest (jaxcost's
        # per-metric max convention), meet the branch out layouts
        probes = []
        for bi, br in enumerate(branches):
            cost, outs = self._probe(
                lambda br=br: self.run(br, operands,
                                       f"{path}/branch", mult))
            probes.append((cost, bi, outs))
        cost, heavy, _ = max(probes, key=lambda t: (t[0], -t[1]))
        outs = self.run(branches[heavy], operands,
                        f"{path}/branches[{heavy}]", mult)
        for _, _, other in probes:
            outs = [_meet(a, b) for a, b in zip(outs, other)]
        for v, s in zip(eqn.outvars, outs):
            self.write(v, s)

    def _h_transparent(self, eqn, path: str, mult: int) -> None:
        body = eqn.params.get("call_jaxpr") or eqn.params.get("jaxpr")
        raw = getattr(body, "jaxpr", body) if body is not None else None
        if raw is None or len(raw.invars) != len(eqn.invars):
            for v in eqn.outvars:
                self.write(v, _replicated(len(v.aval.shape)))
            self.note(f"opaque call {eqn.primitive.name}: outputs "
                      f"treated as replicated")
            return
        outs = self.run(body, [self.read(v) for v in eqn.invars],
                        f"{path}/{eqn.primitive.name}", mult)
        for v, s in zip(eqn.outvars, outs):
            self.write(v, s)


def _lit(v) -> bool:
    return type(v).__name__ == "Literal" or hasattr(v, "val")


def _meet(a: tuple, b: tuple) -> tuple:
    """Join two layouts of the same value: keep agreeing entries, drop
    the rest to unsharded (conservative: disagreement means GSPMD will
    pick one and reshard the other; we model the value as needing the
    common denominator)."""
    if a == b:
        return a
    return tuple(x if x == y else None for x, y in zip(a, b))


def _strip_axes(spec: tuple, axes) -> tuple:
    kill = set(axes)
    out = []
    for e in spec:
        kept = tuple(a for a in (e or ()) if a not in kill)
        out.append(kept or None)
    return tuple(out)


def _map_reshape(in_shape, out_shape, spec, sizes):
    """Propagate a per-dim spec through reshape by factor grouping.
    Returns (out_spec, lost_axes): a sharded in-dim survives a split if
    it lands on the leading factor and the shard count divides it, and
    survives a merge if it is the group's leading in-dim; anything else
    is a resharding (GSPMD re-tiles) and its axes are `lost`."""
    out = [None] * len(out_shape)
    lost: List[str] = []
    i = j = 0
    while i < len(in_shape) or j < len(out_shape):
        gi, gj = [i], [j]
        pi = in_shape[i] if i < len(in_shape) else 1
        pj = out_shape[j] if j < len(out_shape) else 1
        while pi != pj:
            if pi < pj and len(gi) + gi[0] < len(in_shape):
                gi.append(gi[0] + len(gi))
                pi *= in_shape[gi[-1]]
            elif pj < pi and len(gj) + gj[0] < len(out_shape):
                gj.append(gj[0] + len(gj))
                pj *= out_shape[gj[-1]]
            else:
                break
        group_axes = [a for d in gi if d < len(spec)
                      for a in (spec[d] or ())]
        if len(gi) == 1 and len(gj) == 1:
            if gi[0] < len(spec):
                out[gj[0]] = spec[gi[0]]
        elif group_axes:
            lead = gi[0]
            lead_entry = spec[lead] if lead < len(spec) else None
            others = [a for d in gi[1:] if d < len(spec)
                      for a in (spec[d] or ())]
            factor = 1
            for a in (lead_entry or ()):
                factor *= sizes.get(a, 1)
            if others:
                lost.extend(group_axes)      # non-leading factor sharded
            elif lead_entry and out_shape[gj[0]] % max(factor, 1) == 0:
                out[gj[0]] = lead_entry      # rides the leading factor
            elif lead_entry:
                lost.extend(lead_entry)
        i = gi[-1] + 1
        j = gj[-1] + 1
    return out, sorted(set(lost))


# --------------------------------------------------------------- analysis
def analyze_jit(fn, *args, name: str, mesh,
                envelope: Optional[int] = None,
                suppress: Optional[Dict[str, str]] = None,
                ) -> ShardReport:
    """Analyze one jitted callable. The trace must stage a single pjit
    equation (any jax.jit-wrapped fn does); its in/out shardings and
    donated_invars seed the interpreter and the donation detector."""
    sizes = _mesh_sizes(mesh)
    closed = jax.make_jaxpr(fn)(*args)
    outer = closed.jaxpr
    pj = [e for e in outer.eqns if e.primitive.name == "jit"]
    if len(outer.eqns) != 1 or not pj:
        raise ValueError(
            f"{name}: expected a single top-level pjit equation "
            f"(wrap the program in jax.jit), got "
            f"{[e.primitive.name for e in outer.eqns]}")
    eqn = pj[0]
    inner = eqn.params["jaxpr"]
    in_sh = eqn.params.get("in_shardings", ())
    out_sh = eqn.params.get("out_shardings", ())
    donated = eqn.params.get("donated_invars",
                             (False,) * len(eqn.invars))

    interp = _Interp(name, sizes)
    entry = []
    for i, v in enumerate(eqn.invars):
        sh = in_sh[i] if i < len(in_sh) else None
        pspec = getattr(sh, "spec", None)
        ndim = len(v.aval.shape)
        entry.append(_spec_of_pspec(pspec, ndim, sizes)
                     if pspec is not None else _replicated(ndim))
    body_out = interp.run(inner, entry, name)
    final_out = []
    for i, v in enumerate(inner.jaxpr.outvars):
        spec = body_out[i]
        sh = out_sh[i] if i < len(out_sh) else None
        pspec = getattr(sh, "spec", None)
        if pspec is not None:
            tgt = _spec_of_pspec(pspec, len(v.aval.shape), sizes)
            interp.transition(spec, tgt, v.aval, f"{name}/out{i}",
                              "pjit_out", 1)
            spec = tgt
        final_out.append(spec)

    if envelope is None:
        envelope = _default_envelope()
    report = ShardReport(name=name, mesh=dict(sizes),
                         edges=interp.acc.edges,
                         implicit_axis_bytes=interp.acc.implicit,
                         explicit_axis_bytes=interp.acc.explicit,
                         envelope_bytes=envelope,
                         notes=interp.acc.notes)

    # per-device peak: liveness with every buffer divided by its true
    # shard factor (vars the interpreter never saw count full-size)
    def _pd_bytes(v):
        b = var_bytes(v)
        spec = interp.specs.get(v)
        if b and spec is not None:
            b //= max(_shard_factor(spec, sizes), 1)
        return b

    rep = peak_live_bytes(inner, name=name, bytes_fn=_pd_bytes)
    report.per_device_peak_bytes = rep.peak_bytes
    report.peak_where = rep.where

    _collect_findings(report, interp, eqn, inner, entry, final_out,
                      body_out, donated, sizes)
    _apply_suppressions(report, suppress or {})
    return report


def _default_envelope() -> int:
    from . import jaxplan
    plan = jaxplan.load_plan()
    if plan and "envelope_bytes" in plan:
        return int(plan["envelope_bytes"])
    return jaxplan.DEFAULT_HBM_ENVELOPE


def _collect_findings(report, interp, eqn, inner, entry, final_out,
                      body_out, donated, sizes) -> None:
    # implicit-collective groups >= IMPLICIT_MIN_BYTES, keyed by
    # (kind, axes) so a backward pass's N gradient psums triage as one
    groups: Dict[str, ShardFinding] = {}
    for edge in report.edges:
        key = (f"implicit:{edge.kind}:"
               f"{'+'.join(sorted(edge.axis_bytes))}")
        b = sum(edge.axis_bytes.values())
        if key in groups:
            g = groups[key]
            g.count += 1
            g.nbytes += b
        else:
            groups[key] = ShardFinding(
                key=key, kind="implicit",
                message=f"implicit {edge.kind} over "
                        f"{'+'.join(sorted(edge.axis_bytes))}",
                nbytes=b, example=f"{edge.path} ({edge.primitive} "
                                  f"{edge.src}->{edge.dst})")
    for g in groups.values():
        if g.nbytes >= IMPLICIT_MIN_BYTES:
            g.message += (f": {g.count} site(s), {g.nbytes:,} wire "
                          f"bytes — first at {g.example}")
            report.findings.append(g)

    # accidental full replication of >= 1 MiB tensors
    repl: Dict[str, ShardFinding] = {}
    for what, path, nbytes in interp.acc.repl_sites:
        key = f"replication:{what}"
        if key in repl:
            repl[key].count += 1
            repl[key].nbytes = max(repl[key].nbytes, nbytes)
        else:
            repl[key] = ShardFinding(
                key=key, kind="replication",
                message=f"{nbytes:,}B tensor gathered to full "
                        f"replication at {path}",
                nbytes=nbytes, example=path)
    report.findings.extend(repl.values())

    # donation defeated by sharding: greedy aval-match of donated
    # invars to outputs (jaxcost's audit convention), then compare the
    # layouts across the aliasing
    taken = set()
    invars = list(eqn.invars)
    outvars = list(inner.jaxpr.outvars)
    inset = set(id(v) for v in invars)
    for i, (v, don) in enumerate(zip(invars, donated)):
        if not don or var_bytes(v) < 1024:
            continue
        match = None
        for j, ov in enumerate(outvars):
            if j in taken or _lit(ov) or id(ov) in inset:
                continue
            if (tuple(ov.aval.shape) == tuple(v.aval.shape)
                    and ov.aval.dtype == v.aval.dtype):
                match = j
                break
        if match is None:
            continue
        taken.add(match)
        in_spec = entry[i]
        out_spec = final_out[match]
        produced = body_out[match]
        if in_spec != out_spec:
            report.findings.append(ShardFinding(
                key=f"donation:defeated:{i}",
                kind="donation",
                message=f"donated invar {i} {_spec_str(in_spec)} "
                        f"aliases output {match} "
                        f"{_spec_str(out_spec)}: layouts differ, "
                        f"aliasing is defeated",
                nbytes=var_bytes(v), example=f"invar{i}->out{match}"))
        elif produced != out_spec and any(produced):
            report.findings.append(ShardFinding(
                key=f"donation:reshard:{i}",
                kind="donation",
                message=f"donated invar {i}'s aliased output {match} "
                        f"is produced {_spec_str(produced)} but held "
                        f"{_spec_str(out_spec)}: XLA gathers into the "
                        f"donated buffer",
                nbytes=var_bytes(v), example=f"invar{i}->out{match}"))

    if report.per_device_peak_bytes > report.envelope_bytes:
        report.findings.append(ShardFinding(
            key="envelope", kind="envelope",
            message=f"per-device peak "
                    f"{report.per_device_peak_bytes:,}B exceeds the "
                    f"jaxplan HBM envelope {report.envelope_bytes:,}B",
            nbytes=report.per_device_peak_bytes))
    report.findings.sort(key=lambda f: f.key)


def _apply_suppressions(report: ShardReport,
                        suppress: Dict[str, str]) -> None:
    unused = dict(suppress)
    for f in report.findings:
        if f.key in unused:
            f.suppressed = unused.pop(f.key)
    for key, reason in sorted(unused.items()):
        report.notes.append(
            f"unused suppression {key!r} ({reason}) — the finding it "
            f"triaged no longer fires")


# --------------------------------------------------------------- registry
@dataclass(frozen=True)
class _ShardProgram:
    name: str
    #: () -> (jitted_fn, args, mesh); lazy so building one program
    #: never traces the others
    build: Callable
    #: finding key -> triage reason (the committed suppressions)
    suppress: Dict[str, str] = field(default_factory=dict)


def _need_devices(n: int):
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"sharding registry programs need >= {n} devices; run "
            f"under XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            f"(the jaxshard CLI and tests/conftest.py both set this)")
    return devs


@functools.lru_cache(maxsize=1)
def _tp_train_setup():
    """The fsdp x tp flagship: ZeRO-1 ShardedTrainStep of a TP-marked
    GPT on a sharding=2 x tp=2 mesh (SNIPPETS.md [2] layouts). Params
    stay replicated while optimizer moments shard over 'sharding' —
    the weight-update-sharding layout of arxiv 2004.13336, whose
    implicit allgather-into-donated-params is exactly what the
    donation detector must see."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from ..models.gpt import GPT, GPTConfig, gpt_loss_fn
    from ..parallel.api import ShardedTrainStep, ShardingStage
    from ..parallel.mesh import build_mesh, set_global_mesh

    devs = _need_devices(4)
    mesh = build_mesh(sharding=2, tp=2, devices=devs[:4])
    set_global_mesh(mesh)
    paddle.seed(0)
    # vocab x hidden sized so wte / lm_head cross the 1 MiB
    # replication threshold (f32 2048 x 128 = 1 MiB)
    cfg = GPTConfig(vocab_size=2048, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=32)
    model = GPT(cfg)
    optim = popt.AdamW(1e-3, parameters=model.parameters())
    step = ShardedTrainStep(model, gpt_loss_fn, optim, mesh=mesh,
                            sharding_stage=ShardingStage.OPTIMIZER)
    x = paddle.to_tensor(np.zeros((4, 32), np.int64))
    y = paddle.to_tensor(np.zeros((4, 32), np.int64))
    return step, x, y, mesh


def _traced_sharded_step(step, x, y):
    """The jitted step fn + example args, mirroring
    ShardedTrainStep._lowered's assembly without compiling."""
    import jax.numpy as jnp

    params, frozen = step._split_params()
    buffers = {k: b._value for k, b in step.model.named_buffers()
               if b is not None}
    opt_state = step._opt_state or step.optimizer.init_opt_state(params)
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    arr = [a._value for a in (x, y)]
    if step._jitted is None:
        step._build(params, frozen, buffers, opt_state, arr)
    args = (params, frozen, buffers, opt_state, acc,
            jnp.asarray(True), jnp.asarray(1e-3, jnp.float32),
            jax.random.PRNGKey(0), *arr)
    return step._jitted, args


def _prog_train_fsdp_tp():
    step, x, y, mesh = _tp_train_setup()
    fn, args = _traced_sharded_step(step, x, y)
    return fn, args, mesh


def _prog_train_dp():
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from ..models.gpt import GPT, GPTConfig, gpt_loss_fn
    from ..parallel.api import ShardedTrainStep
    from ..parallel.mesh import build_mesh, set_global_mesh

    devs = _need_devices(4)
    mesh = build_mesh(dp=4, devices=devs[:4])
    set_global_mesh(mesh)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=24)
    model = GPT(cfg)
    optim = popt.SGD(learning_rate=0.1, parameters=model.parameters())
    step = ShardedTrainStep(model, gpt_loss_fn, optim, mesh=mesh)
    x = paddle.to_tensor(np.zeros((4, 24), np.int64))
    y = paddle.to_tensor(np.zeros((4, 24), np.int64))
    fn, args = _traced_sharded_step(step, x, y)
    return fn, args, mesh


def _collective_mesh_programs():
    """The three explicit-collective programs, IDENTICAL shapes to
    jaxcost's `collective.*` registry entries: their per-axis explicit
    bytes must sum to jaxcost's committed comm_bytes (enforced by
    tools/jaxcost.py's cross-artifact check)."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..parallel import shard_map
    from ..parallel.ring_attention import (ring_attention,
                                           ulysses_attention)

    devs = _need_devices(4)
    mesh = Mesh(np.asarray(devs[:4]), ("sp",))
    B, H, T, D = 1, 4, 32, 8
    q = jnp.zeros((B, H, T, D), jnp.float32)
    # ptlint: disable=PT-S001  this IS the committed layout (mirrors
    # jaxcost's collective.* literals so both artifacts budget the
    # same program)
    spec = P(None, None, "sp", None)

    ring = shard_map(lambda a, b, c: ring_attention(a, b, c, "sp"),
                     mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                     axis_names={"sp"})
    uly = shard_map(lambda a, b, c: ulysses_attention(a, b, c, "sp"),
                    mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                    axis_names={"sp"})

    def psum_tree(grads):
        return jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, "dp"), grads)

    dmesh = Mesh(np.asarray(devs[:4]), ("dp",))
    tree = {"w": jnp.zeros((8, 8), jnp.float32),
            "b": jnp.zeros((4,), jnp.float32)}
    pt = shard_map(psum_tree, mesh=dmesh,
                   # ptlint: disable=PT-S001  committed registry layout
                   in_specs=({"w": P("dp", None), "b": P("dp")},),
                   # ptlint: disable=PT-S001  committed registry layout
                   out_specs={"w": P(None, None), "b": P(None)},
                   check_vma=False)
    return [
        ("collective.ring_attention", jax.jit(ring), (q, q, q), mesh),
        ("collective.ulysses_attention", jax.jit(uly), (q, q, q),
         mesh),
        ("collective.psum_tree", jax.jit(pt), (tree,), dmesh),
    ]


def _tp_param_specs(params, tp_axis="tp"):
    """Megatron layout for the flat serving param dict: column-parallel
    qkv/up/lm_head, row-parallel out/down, vocab-parallel wte."""
    from jax.sharding import PartitionSpec as P

    def spec(k):
        if k.endswith(("attn.qkv.weight", "mlp.up.weight",
                       "lm_head.weight")):
            return P(None, tp_axis)
        if k.endswith(("attn.qkv.bias", "mlp.up.bias")):
            return P(tp_axis)
        if k.endswith(("attn.out.weight", "mlp.down.weight")):
            return P(tp_axis, None)
        if k == "wte.weight":
            return P(tp_axis, None)
        return P()

    return {k: spec(k) for k in params}


@functools.lru_cache(maxsize=1)
def _serving_tp_setup():
    import paddle_tpu as paddle
    from ..models import generation
    from ..models.gpt import GPT, GPTConfig
    from ..parallel.mesh import build_mesh, set_global_mesh

    devs = _need_devices(4)
    mesh = build_mesh(tp=4, devices=devs[:4])
    set_global_mesh(None)  # serving programs carry explicit shardings
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=32768, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=32)
    model = GPT(cfg)
    geom = (cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.max_seq_len)
    params = generation.extract_params(model)
    return params, geom, mesh


def _named(mesh, pspec):
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, pspec)


def _serving_decode_programs():
    from jax.sharding import PartitionSpec as P

    from ..models import generation as g

    params, geom, mesh = _serving_tp_setup()
    L, H, D, S = geom
    C = H * D
    dtype = jnp.asarray(params["wte.weight"]).dtype
    B = 8
    psh = {k: _named(mesh, v)
           for k, v in _tp_param_specs(params).items()}
    repl = _named(mesh, P())
    # ptlint: disable=PT-S001  this IS the committed serving layout:
    # the registry defines the head-sharded KV contract the plan pins
    head_sh = _named(mesh, P(None, "tp", None, None))

    tokens = jnp.zeros((B,), jnp.int32)
    positions = jnp.zeros((B,), jnp.int32)
    x = jnp.zeros((B, 1, C), dtype)
    q = jnp.zeros((B, H, 1, D), dtype)
    kc = jnp.zeros((B, H, S, D), dtype)

    embed = jax.jit(lambda p, t, pos: g._token_embed(p, t, pos),
                    in_shardings=(psh, repl, repl),
                    out_shardings=repl)
    qkv = jax.jit(lambda p, xx: g._decode_qkv(p, 0, xx, geom),
                  in_shardings=(psh, repl))
    attn = jax.jit(
        lambda p, xx, qq, k, v, pos: g._decode_attn(
            p, 0, xx, qq, k, v, pos, geom),
        in_shardings=(psh, repl, head_sh, head_sh, head_sh, repl),
        out_shardings=repl)
    head = jax.jit(lambda p, xx: g._decode_head(p, xx),
                   in_shardings=(psh, repl), out_shardings=repl)
    return [
        ("serving.token_embed.tp", embed, (params, tokens, positions),
         mesh),
        ("serving.decode_qkv.tp", qkv, (params, x), mesh),
        ("serving.decode_attn.tp", attn,
         (params, x, q, kc, kc, positions), mesh),
        ("serving.decode_head.tp", head, (params, x), mesh),
    ]


def _prog_cache_write_tp():
    """The donated paged-cache write under head sharding: kc/vc are
    donated AND hold the same head-sharded layout in and out — the
    donation true-negative the plan pins (contrast with the training
    step's donation:reshard hit)."""
    from jax.sharding import PartitionSpec as P

    from ..models import generation as g

    params, geom, mesh = _serving_tp_setup()
    L, H, D, S = geom
    dtype = jnp.asarray(params["wte.weight"]).dtype
    B = 8
    # ptlint: disable=PT-S001  committed registry layout (head-sharded
    # KV donation true-negative the plan pins)
    head_sh = _named(mesh, P(None, "tp", None, None))
    repl = _named(mesh, P())
    kc = jnp.zeros((B, H, S, D), dtype)
    k_new = jnp.zeros((B, H, 1, D), dtype)
    pos = jnp.zeros((), jnp.int32)
    fn = jax.jit(
        lambda kc, vc, kn, vn, p: g._cache_write.__wrapped__(
            kc, vc, kn, vn, p),
        in_shardings=(head_sh, head_sh, head_sh, head_sh, repl),
        out_shardings=(head_sh, head_sh),
        # ptlint: disable=PT-T009  deliberately mirrors generation.
        # _cache_write's planned donation so the analyzer can prove the
        # head-sharded in==out layout keeps the aliasing intact (the
        # donation true-negative this registry program exists to pin)
        donate_argnums=(0, 1))
    return fn, (kc, kc, k_new, k_new, pos), mesh


# The committed registry. Suppression reasons ARE the triage record —
# the plan cannot be written while any finding lacks one.
_SHARD_REGISTRY: Tuple[_ShardProgram, ...] = (
    _ShardProgram(
        "train_step.fsdp_tp", _prog_train_fsdp_tp,
        suppress={
            "implicit:psum:tp":
                "Megatron tp reductions by design: the vocab-parallel "
                "wte lookup (masked local gather + psum) and the "
                "RowParallelLinear contractions (attn.out / mlp.down "
                "contract the tp-sharded inner dim), one all-reduce "
                "per block pair (distributed/tp_layers.py)",
            "implicit:psum:sharding":
                "data-parallel gradient synchronization over the "
                "'sharding' axis; with ZeRO-1 moments XLA lowers this "
                "psum + sharded update to reduce-scatter + allgather "
                "(weight-update sharding, arxiv 2004.13336)",
            "implicit:all_gather:sharding":
                "ZeRO-1 weight-update allgather: params stay "
                "replicated while updates are computed over sharded "
                "moments, so the new params gather over 'sharding' "
                "once per step — intentional (stage-1 trades this "
                "gather for sharded optimizer state)",
            "implicit:all_gather:tp":
                "lm_head gather_output=True: the vocab-sharded logits "
                "gather at the loss flatten so cross-entropy sees the "
                "full vocab (tp_layers.ColumnParallelLinear)",
            "donation:reshard:27":
                "REAL HIT (triaged, intentional): the donated params "
                "pytree (flat invar 27) aliases a new param produced "
                "through the ZeRO-1 'sharding' weight-update path — "
                "XLA materializes the gathered copy before writing "
                "the donated buffer. Keeping stage-1 semantics; "
                "stage-3 (PARAMETER) removes the gather by keeping "
                "params sharded",
        }),
    _ShardProgram(
        "train_step.dp", _prog_train_dp,
        suppress={
            "implicit:psum:dp":
                "the data-parallel gradient all-reduce: every grad "
                "dot contracts the dp-sharded batch dim (this IS the "
                "allreduce jaxcost charges explicitly in "
                "collective.psum_tree)",
        }),
    _ShardProgram("collective.ring_attention", None),
    _ShardProgram("collective.ulysses_attention", None),
    _ShardProgram("collective.psum_tree", None),
    _ShardProgram(
        "serving.token_embed.tp", None,
        suppress={
            "implicit:psum:tp":
                "vocab-parallel embedding lookup: gathering rows from "
                "the tp-sharded wte is lowered as masked local lookup "
                "+ psum (tp_layers.VocabParallelEmbedding semantics)",
        }),
    _ShardProgram(
        "serving.decode_qkv.tp", None,
        suppress={
            "implicit:all_gather:tp":
                "fused qkv [B,1,3C]->[B,1,3,H,D] reshape crosses the "
                "tp-tiled column dim (the split's leading factor 3 is "
                "not divisible by tp=4), so the column shards gather "
                "before re-tiling onto heads — a per-token 3C row, "
                "accepted; the committed serving layout keeps q/k/v "
                "head-sharded after this point",
        }),
    _ShardProgram(
        "serving.decode_attn.tp", None,
        suppress={
            "implicit:psum:tp":
                "REAL HIT (triaged, intentional): the Megatron "
                "row-parallel attention-output reduction — att "
                "[B,1,C] is tp-sharded on C after the head merge and "
                "contracts with the replicated out-projection, one "
                "psum per decode step per layer. This is the quantized-"
                "collective target of ROADMAP item 2",
        }),
    _ShardProgram(
        "serving.decode_head.tp", None,
        suppress={
            "implicit:all_gather:tp":
                "REAL HIT (triaged, intentional): serving logits "
                "[B,V] leave the column-parallel lm_head gathered to "
                "full replication (>=1MiB at vocab 32768) because the "
                "sampler consumes the full vocab row; a sharded "
                "top-k would remove this gather (ROADMAP item 2)",
            "replication:pjit_out:tp":
                "same gather as implicit:all_gather:tp — the "
                "replicated-logits contract of the dense sampler",
        }),
    _ShardProgram("serving.cache_write.tp", _prog_cache_write_tp),
)


def registry_names() -> List[str]:
    return [p.name for p in _SHARD_REGISTRY]


def _build_shard_programs(names: Optional[Sequence[str]] = None):
    known = {p.name: p for p in _SHARD_REGISTRY}
    if names is not None:
        unknown = sorted(set(names) - set(known))
        if unknown:
            raise KeyError(
                f"unknown program(s): {', '.join(unknown)}; known: "
                f"{', '.join(known)}")
    wanted = list(names) if names is not None else list(known)
    out = []
    coll = None
    serv = None
    for name in wanted:
        prog = known[name]
        if prog.build is not None:
            out.append((prog, prog.build))
            continue
        if name.startswith("collective."):
            if coll is None:
                coll = {n: (f, a, m)
                        for n, f, a, m in _collective_mesh_programs()}
            fam = coll
        else:
            if serv is None:
                serv = {n: (f, a, m)
                        for n, f, a, m in _serving_decode_programs()}
            fam = serv
        f, a, m = fam[name]
        out.append((prog, lambda f=f, a=a, m=m: (f, a, m)))
    return out


def compute_reports(names: Optional[Sequence[str]] = None,
                    envelope: Optional[int] = None,
                    ) -> Dict[str, ShardReport]:
    """Analyze every (selected) registry program."""
    reports = {}
    for prog, build in _build_shard_programs(names):
        fn, args, mesh = build()
        reports[prog.name] = analyze_jit(
            fn, *args, name=prog.name, mesh=mesh, envelope=envelope,
            suppress=prog.suppress)
    return reports


# ------------------------------------------------------------ plan I/O
def _plan_payload(reports: Dict[str, ShardReport]) -> dict:
    return {
        "version": PLAN_VERSION,
        "tolerance": DEFAULT_TOLERANCE,
        "envelope_bytes": next(iter(reports.values())).envelope_bytes
        if reports else _default_envelope(),
        "programs": {name: rep.to_dict()
                     for name, rep in sorted(reports.items())},
    }


def write_plan(path: str, reports: Dict[str, ShardReport]) -> dict:
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
    """Committed shard plan, or None when missing. stdlib-only."""
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return None
    return _load_plan_cached(path, mtime)


def committed_shard_factors(path: str = DEFAULT_PLAN_PATH
                            ) -> Dict[str, Dict[str, int]]:
    """program name -> mesh axis sizes from the committed plan (the
    shard factors jaxcost's cross-artifact check consumes)."""
    plan = load_plan(path)
    if not plan:
        return {}
    return {name: dict(entry.get("mesh", {}))
            for name, entry in plan.get("programs", {}).items()}


def _num_drift(cur, ref, tol: float) -> bool:
    lo, hi = sorted((float(cur), float(ref)))
    return hi - lo > tol * max(hi, 1.0)


def diff_plans(committed: dict, current: dict,
               tolerance: Optional[float] = None) -> List[str]:
    """Violations between a committed plan and a freshly computed one:
    coverage both directions, structural drift exact, bytes within
    tolerance."""
    tol = tolerance if tolerance is not None else float(
        committed.get("tolerance", DEFAULT_TOLERANCE))
    out: List[str] = []
    cp = committed.get("programs", {})
    np_ = current.get("programs", {})
    for name in sorted(set(cp) - set(np_)):
        out.append(f"{name}: committed but no longer in the registry")
    for name in sorted(set(np_) - set(cp)):
        out.append(f"{name}: registry program missing from the "
                   f"committed plan")
    for name in sorted(set(cp) & set(np_)):
        a, b = cp[name], np_[name]
        if a.get("mesh") != b.get("mesh"):
            out.append(f"{name}: mesh drift {a.get('mesh')} -> "
                       f"{b.get('mesh')}")
        if int(a.get("edge_count", 0)) != int(b.get("edge_count", 0)):
            out.append(f"{name}: resharding edge count "
                       f"{a.get('edge_count')} -> "
                       f"{b.get('edge_count')}")
        if bool(a.get("envelope_ok", True)) \
                != bool(b.get("envelope_ok", True)):
            out.append(f"{name}: envelope_ok flipped "
                       f"{a.get('envelope_ok')} -> "
                       f"{b.get('envelope_ok')}")
        for fieldname in ("implicit_axis_bytes", "explicit_axis_bytes"):
            fa, fb = a.get(fieldname, {}), b.get(fieldname, {})
            if sorted(fa) != sorted(fb):
                out.append(f"{name}: {fieldname} axes "
                           f"{sorted(fa)} -> {sorted(fb)}")
                continue
            for ax in fa:
                if _num_drift(fb[ax], fa[ax], tol):
                    out.append(
                        f"{name}: {fieldname}[{ax}] drifted "
                        f"{fa[ax]:,} -> {fb[ax]:,} (> {tol:.0%})")
        for fieldname in ("comm_bytes_total", "per_device_peak_bytes"):
            if _num_drift(b.get(fieldname, 0), a.get(fieldname, 0),
                          tol):
                out.append(f"{name}: {fieldname} drifted "
                           f"{a.get(fieldname, 0):,} -> "
                           f"{b.get(fieldname, 0):,} (> {tol:.0%})")
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
    return out


def unsuppressed_findings(reports: Dict[str, ShardReport]
                          ) -> List[str]:
    out = []
    for name, rep in sorted(reports.items()):
        for f in rep.unsuppressed():
            out.append(f"{name}: {f.key}: {f.message}")
    return out


def check_plan(path: str = DEFAULT_PLAN_PATH,
               reports: Optional[Dict[str, ShardReport]] = None,
               ) -> List[str]:
    """Violations of the committed plan: missing/stale file, version
    drift, structural/numeric drift vs a fresh analysis, and any
    unsuppressed finding."""
    committed = load_plan(path)
    if committed is None:
        return [f"no committed shard plan at {path} — run "
                f"tools/jaxshard.py --plan write"]
    if committed.get("version") != PLAN_VERSION:
        return [f"plan version {committed.get('version')} != analyzer "
                f"version {PLAN_VERSION} — re-write the plan"]
    if reports is None:
        reports = compute_reports(
            envelope=int(committed.get("envelope_bytes", 0)) or None)
    out = unsuppressed_findings(reports)
    out += diff_plans(committed, _plan_payload(reports))
    return out


# --------------------------------------------------- cross-artifact check
def crosscheck_with_budget(budget: dict,
                           plan_path: str = DEFAULT_PLAN_PATH,
                           tolerance: Optional[float] = None,
                           ) -> List[str]:
    """jaxcost x jaxshard consistency: for every program committed in
    BOTH artifacts, jaxshard's explicit per-axis bytes must sum to
    jaxcost's comm_bytes (same byte table, so disagreement means one
    artifact is stale). stdlib-only; returns violation strings."""
    plan = load_plan(plan_path)
    if not plan:
        return []  # no shard plan committed yet: nothing to check
    tol = tolerance if tolerance is not None else float(
        plan.get("tolerance", DEFAULT_TOLERANCE))
    out: List[str] = []
    budget_programs = budget.get("programs", {})
    for name, entry in sorted(plan.get("programs", {}).items()):
        if name not in budget_programs:
            continue
        shard_comm = sum(entry.get("explicit_axis_bytes", {}).values())
        cost_comm = int(budget_programs[name].get("comm_bytes", 0))
        if _num_drift(shard_comm, cost_comm, tol):
            out.append(
                f"{name}: jaxshard explicit collective bytes "
                f"{shard_comm:,} disagree with jaxcost comm_bytes "
                f"{cost_comm:,} (> {tol:.0%}) — shardplan.json and "
                f"jaxcost_budget.json have drifted apart")
    return out
