"""jaxcost: static FLOP / bytes / peak-memory analyzer for jaxprs.

PR 4's trace-time auditor checks what a compiled program DOES (host
callbacks, const bloat, downcasts); this module checks what it COSTS —
without running it. An abstract interpreter walks the jaxpr (the same
`_sub_jaxprs` traversal the auditor uses) and computes, per program:

- **flops** — per-primitive cost table: matmuls/convs from their
  contraction geometry, transcendentals at 8 flops/element, reductions
  at one flop per input element, data movement at zero, everything
  else conservatively at one flop per output element;
- **bytes_read / bytes_written** — operand and result bytes per
  equation (literals are inlined and free);
- **comm_bytes** — collective wire volume: ring all-reduce moves ~2x
  its payload (reduce-scatter + all-gather phases), all_gather is
  charged its output, permutes/all_to_all their input;
- **peak_bytes** — linear-scan liveness (`liveness.py`): buffers are
  freed after their last read, loop carries double-reside at iteration
  boundaries, sub-programs contribute their transient overshoot;
- **donation audit** — arguments that die after their last read AND
  have an aval-matched output produced no earlier are donation
  candidates: not listing them in `donate_argnums` costs a full extra
  residency of their bytes.

Control flow: `scan` bodies are multiplied by their static trip count
(`fori_loop` with static bounds lowers to scan, so ring attention's
rotation is counted exactly), `while` bodies are counted ONCE with a
note (trip count is not static), `cond` takes the per-metric max over
branches, `pjit`/`shard_map`/custom_* recurse transparently. Inside
`shard_map` the avals are per-device, so collective programs report
per-device cost — the quantity weak scaling holds constant.

The numbers are a deterministic MODEL, not a measurement: XLA fusion
changes bytes in its favor and the flop table rounds transcendentals,
so absolute values are first-order. What makes them useful is that
they are exactly reproducible from the IR — `jaxcost_budget.json`
pins them per registered program and `tools/jaxcost.py --budget
check` fails when a code change moves any metric more than 5%, the
same regression contract as ptlint's baseline.

Registered programs (`registry_names()`): jit.TrainStep on the tiny
deterministic GPT ptlint audits, the five decode sub-programs shared
by dense generate() and paged serving (models/generation.py), the
serving prefill + paged-attention decode step, and the distributed
collective paths (ring/ulysses attention, the psum tree) on a 4-device
mesh.
"""
from __future__ import annotations

import functools
import json
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .hlo_bytes import shape_bytes  # noqa: F401  (one byte-accounting table)
from .jaxpr_audit import _sub_jaxprs
from .liveness import aval_bytes, peak_live_bytes, var_bytes

__all__ = ["ProgramCost", "analyze_jaxpr", "estimate_fn",
           "estimate_train_step", "estimate_decode_step",
           "DonationFinding", "leaf_argnums", "audit_donation",
           "registry_names", "compute_costs",
           "collect_donation_findings", "write_budget", "check_budget",
           "DEFAULT_TOLERANCE", "shape_bytes"]

# --------------------------------------------------------------- cost tables
#: pure data movement / bookkeeping: no arithmetic charged
_ZERO_FLOP = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "slice", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "pad", "gather", "scatter",
    "squeeze", "expand_dims", "rev", "iota", "copy", "copy_p",
    "convert_element_type", "bitcast_convert_type", "stop_gradient",
    "select_n", "split", "device_put", "sharding_constraint", "pbroadcast",
    "axis_index", "real", "imag", "is_finite", "sign",
})
#: one table entry = 8 flops per output element (polynomial approx cost)
_TRANSCENDENTAL = frozenset({
    "exp", "exp2", "expm1", "log", "log2", "log1p", "tanh", "sinh",
    "cosh", "tan", "sin", "cos", "asin", "acos", "atan", "atan2",
    "asinh", "acosh", "atanh", "erf", "erfc", "erf_inv", "logistic",
    "pow", "integer_pow", "sqrt", "rsqrt", "cbrt", "digamma", "lgamma",
    "threefry2x32",
})
_TRANSCENDENTAL_FLOPS = 8
#: reductions cost one flop per INPUT element
_REDUCTION_PREFIXES = ("reduce_", "cum", "arg")

#: collectives: wire bytes per equation. Ring all-reduce moves
#: 2*(N-1)/N * payload per device (~2x); gathers are charged their
#: output; permutes / all-to-all / scatters their input.
_COMM_TWICE_IN = frozenset({"psum", "psum2", "pmax", "pmin", "pmax2",
                            "pmin2", "pmean"})
_COMM_OUT = frozenset({"all_gather", "all_gather_invariant"})
_COMM_IN = frozenset({"reduce_scatter", "psum_scatter", "ppermute",
                      "pshuffle", "all_to_all"})


def _elems(v) -> int:
    aval = getattr(v, "aval", None)
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


def _leaf_flops(eqn) -> int:
    name = eqn.primitive.name
    out_elems = sum(_elems(v) for v in eqn.outvars)
    if name in _ZERO_FLOP or name in _COMM_TWICE_IN or name in _COMM_OUT \
            or name in _COMM_IN:
        return 0
    if name == "dot_general":
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        lhs_shape = eqn.invars[0].aval.shape
        csize = 1
        for d in lhs_c:
            csize *= int(lhs_shape[d])
        return 2 * out_elems * csize
    if name == "conv_general_dilated":
        dn = eqn.params["dimension_numbers"]
        rhs = eqn.invars[1].aval
        out_feature = int(rhs.shape[dn.rhs_spec[0]])
        kernel_elems = _elems(eqn.invars[1]) // max(out_feature, 1)
        return 2 * out_elems * kernel_elems
    if name in _TRANSCENDENTAL:
        return _TRANSCENDENTAL_FLOPS * out_elems
    if name.startswith(_REDUCTION_PREFIXES):
        return sum(_elems(v) for v in eqn.invars
                   if not hasattr(v, "val"))
    return out_elems  # conservative default: 1 flop / output element


def _leaf_comm(eqn) -> int:
    name = eqn.primitive.name
    in_bytes = sum(var_bytes(v) for v in eqn.invars)
    if name in _COMM_TWICE_IN:
        return 2 * in_bytes
    if name in _COMM_OUT:
        return sum(var_bytes(v) for v in eqn.outvars)
    if name in _COMM_IN:
        return in_bytes
    return 0


# ------------------------------------------------------------------ analyzer
@dataclass
class ProgramCost:
    name: str
    flops: int = 0
    bytes_read: int = 0
    bytes_written: int = 0
    comm_bytes: int = 0
    peak_bytes: int = 0
    peak_at: str = ""
    #: primitive -> {count, flops, bytes, comm_bytes}; counts are DYNAMIC
    #: instances (a scan body eqn counts once per trip)
    by_primitive: Dict[str, Dict[str, int]] = field(default_factory=dict)
    notes: Tuple[str, ...] = ()

    def to_dict(self) -> dict:
        return {"name": self.name, "flops": self.flops,
                "bytes_read": self.bytes_read,
                "bytes_written": self.bytes_written,
                "comm_bytes": self.comm_bytes,
                "peak_bytes": self.peak_bytes, "peak_at": self.peak_at,
                "by_primitive": self.by_primitive,
                "notes": list(self.notes)}

    def format(self, top_n: int = 8) -> str:
        lines = [f"{self.name}: {self.flops:,} flops, "
                 f"read {self.bytes_read:,} B, "
                 f"written {self.bytes_written:,} B, "
                 f"comm {self.comm_bytes:,} B, "
                 f"peak {self.peak_bytes:,} B (at {self.peak_at})"]
        ranked = sorted(self.by_primitive.items(),
                        key=lambda kv: -(kv[1]["flops"] + kv[1]["bytes"]))
        for pname, row in ranked[:top_n]:
            lines.append(f"    {pname:<24} x{row['count']:<6} "
                         f"{row['flops']:>14,} flops "
                         f"{row['bytes']:>14,} B"
                         + (f" {row['comm_bytes']:>12,} B comm"
                            if row["comm_bytes"] else ""))
        for n in self.notes:
            lines.append(f"    note: {n}")
        return "\n".join(lines)


class _Tally:
    __slots__ = ("flops", "read", "written", "comm", "by_prim", "notes")

    def __init__(self):
        self.flops = 0
        self.read = 0
        self.written = 0
        self.comm = 0
        self.by_prim: Dict[str, Dict[str, int]] = {}
        self.notes: List[str] = []

    def charge(self, pname, mult, flops, read, written, comm):
        self.flops += mult * flops
        self.read += mult * read
        self.written += mult * written
        self.comm += mult * comm
        row = self.by_prim.setdefault(
            pname, {"count": 0, "flops": 0, "bytes": 0, "comm_bytes": 0})
        row["count"] += mult
        row["flops"] += mult * flops
        row["bytes"] += mult * (read + written)
        row["comm_bytes"] += mult * comm

    def absorb(self, other: "_Tally", mult: int = 1):
        self.flops += mult * other.flops
        self.read += mult * other.read
        self.written += mult * other.written
        self.comm += mult * other.comm
        for pname, row in other.by_prim.items():
            mine = self.by_prim.setdefault(
                pname,
                {"count": 0, "flops": 0, "bytes": 0, "comm_bytes": 0})
            for k in mine:
                mine[k] += mult * row[k]
        self.notes.extend(other.notes)


def _tally(jaxpr_like, out: _Tally, mult: int, path: str) -> None:
    raw = jaxpr_like.jaxpr if hasattr(jaxpr_like, "jaxpr") else jaxpr_like
    for eqn in raw.eqns:
        pname = eqn.primitive.name
        subs = list(_sub_jaxprs(eqn))
        if not subs:
            read = sum(var_bytes(v) for v in eqn.invars)
            written = sum(var_bytes(v) for v in eqn.outvars)
            out.charge(pname, mult, _leaf_flops(eqn), read, written,
                       _leaf_comm(eqn))
            continue
        # control flow charges only its children (the eqn's own in/out
        # bytes are the body's, already counted inside)
        if pname == "cond":
            branches = []
            for label, sub in subs:
                t = _Tally()
                _tally(sub, t, 1, f"{path}/{pname}.{label}")
                branches.append(t)
            heavy = max(branches,
                        key=lambda t: (t.flops, t.read + t.written))
            # per-metric max over branches (conservative); by_primitive
            # attribution follows the heaviest branch
            out.flops += mult * max(t.flops for t in branches)
            out.read += mult * max(t.read for t in branches)
            out.written += mult * max(t.written for t in branches)
            out.comm += mult * max(t.comm for t in branches)
            for bp, row in heavy.by_prim.items():
                mine = out.by_prim.setdefault(
                    bp, {"count": 0, "flops": 0, "bytes": 0,
                         "comm_bytes": 0})
                for k in mine:
                    mine[k] += mult * row[k]
            out.notes.extend(heavy.notes)
            continue
        m = 1
        if pname == "scan":
            m = int(eqn.params.get("length", 1))
        elif pname == "while":
            out.notes.append(
                f"{path}: 'while' body counted once (trip count is not "
                f"static); totals are a lower bound there")
        for label, sub in subs:
            _tally(sub, out, mult * m, f"{path}/{pname}.{label}")


def analyze_jaxpr(jaxpr_like, name: str = "<jaxpr>") -> ProgramCost:
    """Full static cost of one (Closed)Jaxpr."""
    t = _Tally()
    _tally(jaxpr_like, t, 1, name)
    rep = peak_live_bytes(jaxpr_like, name=name)
    # drop duplicate notes, keep first-seen order
    notes = tuple(dict.fromkeys(t.notes))
    return ProgramCost(name=name, flops=t.flops, bytes_read=t.read,
                       bytes_written=t.written, comm_bytes=t.comm,
                       peak_bytes=rep.peak_bytes, peak_at=rep.where,
                       by_primitive=t.by_prim, notes=notes)


def estimate_fn(fn, *args, static_argnums: Sequence[int] = (),
                name: Optional[str] = None) -> ProgramCost:
    """Trace `fn` on the example args and analyze the result. Accepts
    jax.ShapeDtypeStruct leaves, so big programs can be estimated
    without materializing their buffers."""
    label = name or getattr(fn, "__name__", repr(fn))
    closed = jax.make_jaxpr(
        fn, static_argnums=tuple(static_argnums))(*args)
    return analyze_jaxpr(closed, name=label)


# ----------------------------------------------------------- donation audit
@dataclass(frozen=True)
class DonationFinding:
    program: str
    argnum: int
    nbytes: int
    n_leaves: int
    suppressed: Optional[str] = None  # reason, if intentionally undonated

    @property
    def message(self) -> str:
        return (f"{self.program}: argument {self.argnum} — "
                f"{self.nbytes:,} bytes across {self.n_leaves} array(s) "
                f"dead after their last read with aval-matched outputs; "
                f"add argnum {self.argnum} to donate_argnums to drop a "
                f"full extra residency")

    def format(self) -> str:
        tail = f"  (suppressed: {self.suppressed})" if self.suppressed \
            else ""
        return f"[donation] {self.message}{tail}"


def leaf_argnums(args, static_argnums: Sequence[int] = ()) -> List[int]:
    """argnum of every flattened dynamic-arg leaf, in jaxpr invar order."""
    static = set(static_argnums)
    out: List[int] = []
    for i, a in enumerate(args):
        if i in static:
            continue
        out.extend([i] * len(jax.tree_util.tree_leaves(a)))
    return out


#: below this many matched bytes per argnum the finding is noise (loop
#: counters, lr scalars, per-token activations)
DONATION_MIN_BYTES = 1024


def audit_donation(fn, *args, name: str,
                   donate_argnums: Sequence[int] = (),
                   static_argnums: Sequence[int] = (),
                   min_bytes: int = DONATION_MIN_BYTES,
                   suppress: Optional[Dict[int, str]] = None,
                   ) -> List[DonationFinding]:
    """Flag arguments that could be donated but are not.

    An argnum is a candidate when its leaves (a) are read, (b) are not
    returned unchanged (no passthrough aliasing), and (c) can each be
    greedily matched to a distinct non-passthrough output of identical
    shape+dtype produced at-or-after the leaf's last read — exactly the
    conditions under which XLA's input-output aliasing reuses the
    buffer. Aggregated bytes under `min_bytes` are dropped as noise.
    `suppress` maps argnum -> reason for intentional non-donation; the
    finding is still reported, marked suppressed."""
    suppress = suppress or {}
    closed = jax.make_jaxpr(
        fn, static_argnums=tuple(static_argnums))(*args)
    raw = closed.jaxpr
    owner = leaf_argnums(args, static_argnums)
    if len(owner) != len(raw.invars):
        raise ValueError(
            f"{name}: {len(raw.invars)} jaxpr invars but "
            f"{len(owner)} example-arg leaves — static_argnums "
            f"mismatch?")

    last_read: Dict[object, int] = {}
    produced_at: Dict[object, int] = {}
    for i, eqn in enumerate(raw.eqns):
        for v in eqn.invars:
            if not hasattr(v, "val"):
                last_read[v] = i
        for v in eqn.outvars:
            produced_at[v] = i

    invar_set = set(raw.invars)
    outputs = []  # (shape, dtype, produced_at) of non-passthrough outvars
    for v in raw.outvars:
        if hasattr(v, "val") or v in invar_set:
            continue
        aval = getattr(v, "aval", None)
        outputs.append([tuple(getattr(aval, "shape", ())),
                        getattr(aval, "dtype", None),
                        produced_at.get(v, len(raw.eqns)), False])

    findings: List[DonationFinding] = []
    donated = set(donate_argnums)
    per_argnum: Dict[int, List[object]] = {}
    for v, a in zip(raw.invars, owner):
        per_argnum.setdefault(a, []).append(v)
    for argnum in sorted(per_argnum):
        if argnum in donated:
            continue
        cands = [v for v in per_argnum[argnum]
                 if v in last_read and v not in set(raw.outvars)]
        cands.sort(key=lambda v: last_read[v])
        matched_bytes, matched = 0, 0
        for v in cands:
            aval = getattr(v, "aval", None)
            key = (tuple(getattr(aval, "shape", ())),
                   getattr(aval, "dtype", None))
            for out in outputs:
                if not out[3] and (out[0], out[1]) == key \
                        and out[2] >= last_read[v]:
                    out[3] = True
                    matched_bytes += aval_bytes(aval)
                    matched += 1
                    break
        if matched_bytes >= min_bytes:
            findings.append(DonationFinding(
                program=name, argnum=argnum, nbytes=matched_bytes,
                n_leaves=matched, suppressed=suppress.get(argnum)))
    return findings


# ------------------------------------------------------- high-level helpers
def estimate_train_step(step, *batch,
                        name: str = "train_step") -> ProgramCost:
    """Static cost of a jit.TrainStep's full program (fwd+bwd+optimizer)
    against an example batch — same argument assembly as dispatch."""
    from .jaxpr_audit import train_step_args
    return estimate_fn(step._raw_step, *train_step_args(step, *batch),
                       name=name)


def estimate_decode_step(params, geom, batch: int,
                         dtype=None,
                         name: str = "decode_step") -> ProgramCost:
    """Static cost of ONE full dense decode step (embed + L x (qkv +
    cache write + attn) + head). The KV cache is traced as
    ShapeDtypeStructs so flagship-sized caches cost nothing to model."""
    from ..models import generation as g
    L, H, D, S = geom
    if dtype is None:
        dtype = params["wte.weight"].dtype
    leaf = jax.ShapeDtypeStruct((batch, H, S, D), dtype)
    cache = tuple((leaf, leaf) for _ in range(L))
    tok = jax.ShapeDtypeStruct((batch,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def run(p, c, t, q):
        return g.decode_step(p, c, t, q, geom)

    return estimate_fn(run, params, cache, tok, pos, name=name)


# ----------------------------------------------------------------- registry
@dataclass(frozen=True)
class _Program:
    name: str
    fn: Callable
    args: tuple
    static_argnums: Tuple[int, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    #: argnum -> reason for findings that are intentional
    suppress: Dict[int, str] = field(default_factory=dict)
    #: False for library functions whose donation is the CALLER's jit
    #: decision (shard_map'd collectives)
    donation_applies: bool = True


@functools.lru_cache(maxsize=1)
def _tiny_gpt():
    """The deterministic tiny-GPT recipe ptlint's --audit uses; every
    registry program keys off this geometry so budget numbers are
    stable across machines."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from ..models import generation
    from ..models.gpt import GPT, GPTConfig

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=24)
    model = GPT(cfg)
    geom = (cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.max_seq_len)

    def loss_fn(m, x, y):
        logits = m(x)
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), y.reshape([-1]))

    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = paddle.jit.TrainStep(model, loss_fn, opt)
    params = generation.extract_params(model)
    return model, cfg, geom, params, step


def _train_step_programs() -> List[_Program]:
    import paddle_tpu as paddle
    from .jaxpr_audit import train_step_args
    _, _, _, _, step = _tiny_gpt()
    x = paddle.to_tensor([[1, 2, 3, 4]], dtype="int64")
    y = paddle.to_tensor([[2, 3, 4, 5]], dtype="int64")
    return [_Program("train_step", step._raw_step,
                     tuple(train_step_args(step, x, y)),
                     donate_argnums=step._donate_argnums)]


def _decode_sub_programs() -> List[_Program]:
    from .jaxpr_audit import decode_programs
    _, _, geom, params, _ = _tiny_gpt()
    out = []
    for pname, fn, args, static in decode_programs(params, geom):
        # _cache_write is the one donated decode sub-program: every
        # caller rebinds kc/vc to the returned pair (decode_step,
        # ServingPredictor) so the old cache is reusable in place
        donate = (0, 1) if pname == "cache_write" else ()
        out.append(_Program(f"decode.{pname}",
                            getattr(fn, "__wrapped__", fn), tuple(args),
                            static_argnums=tuple(static),
                            donate_argnums=donate))
    return out


def _serving_programs() -> List[_Program]:
    from ..inference.serving.attention import (PACK_COLS,
                                               fused_decode_chunk,
                                               paged_decode_step)
    from ..inference.serving.paged_cache import write_prefill_scatter
    from ..models import generation as g
    from ..ops.pallas.ragged_paged_attention import \
        ragged_attention_reference
    _, cfg, geom, params, _ = _tiny_gpt()
    L, H, D, S = geom
    dtype = params["wte.weight"].dtype
    ids = jnp.zeros((2, 8), jnp.int32)
    prefill = _Program("serving.prefill",
                       getattr(g.prefill, "__wrapped__", g.prefill),
                       (params, ids, geom), static_argnums=(2,))
    # paged pool geometry: MB * block_size == max_seq_len so the
    # gathered context has the dense cache layout (parity contract)
    bs, nb, N = 4, 8, 2
    MB = S // bs
    pool = jnp.zeros((nb, bs, H, D), dtype)
    pools = tuple((pool, pool) for _ in range(L))
    tokens = jnp.zeros((N,), jnp.int32)
    positions = jnp.zeros((N,), jnp.int32)
    tables = jnp.zeros((N, MB), jnp.int32)
    slots = jnp.zeros((N,), jnp.int32)
    paged = _Program(
        "serving.paged_decode", paged_decode_step,
        (params, pools, tokens, positions, tables, slots, slots, geom),
        static_argnums=(7,),
        suppress={1: "engine crash recovery re-reads the pre-step pools "
                     "to rebuild survivors after a poisoned step "
                     "(LLMEngine watchdog); donating them would delete "
                     "the rollback copy"})
    # the fused k-token chunk (the engine's steady-state decode path):
    # cost scales ~k x the single paged step — the scan body is
    # multiplied by its static trip count — and the pools ARE donated
    # here (the scan carries them; the engine rebinds cache.pools from
    # the return value, and chunk-granular recovery re-prefills from
    # host token logs instead of re-reading pre-step pools)
    # NOTE: there is no per-bucket compile-count axis here anymore — the
    # ragged default pads every batch to the ONE fixed max_num_seqs
    # width, so these budgets each cover every batch mix (pinned by the
    # compile-count test in tests/test_serving_ragged.py).
    K = 8
    packed = jnp.zeros((N, PACK_COLS + K + MB), jnp.int32)
    chunk = _Program(
        "serving.decode_chunk",
        getattr(fused_decode_chunk, "__wrapped__", fused_decode_chunk),
        (params, pools, packed, geom, K, "ragged"),
        static_argnums=(3, 4, 5), donate_argnums=(1,))
    # the ragged paged-attention program: the lax.scan reference is the
    # kernel's cost-faithful twin (same block-streamed flash update the
    # pallas kernel executes per row), so the committed budget bounds
    # the kernel's FLOP/bytes envelope without tracing pallas_call
    q1 = jnp.zeros((N, H, D), dtype)
    lens = jnp.zeros((N,), jnp.int32)
    ragged = _Program(
        "serving.ragged_attention",
        getattr(ragged_attention_reference, "__wrapped__",
                ragged_attention_reference),
        (q1, pool, pool, tables, lens))
    # chunked prefill rides the SAME fused scan (prompt tokens feed the
    # body; no extra dispatch): registering it separately pins that the
    # prompt-feed path adds no cost axis over plain decode — the two
    # budgets must stay identical
    pf_packed = jnp.zeros((N, PACK_COLS + K + MB), jnp.int32)
    chunked_prefill = _Program(
        "serving.chunked_prefill",
        getattr(fused_decode_chunk, "__wrapped__", fused_decode_chunk),
        (params, pools, pf_packed, geom, K, "ragged"),
        static_argnums=(3, 4, 5), donate_argnums=(1,))
    # the dense-admission scatter (PagedKVCache.write_prefill): one
    # program over all layers, keyed on pool geometry and dense-cache
    # shape only — the block ids are always MB long, padded with the
    # out-of-range id nb — with the pools donated (the cache rebinds
    # them from the return value) and the dense cache NOT: batched
    # callers scatter several rows of one dense cache
    dense_leaf = jnp.zeros((N, H, S, D), dtype)
    dense = tuple((dense_leaf, dense_leaf) for _ in range(L))
    write_prefill = _Program(
        "serving.write_prefill",
        getattr(write_prefill_scatter, "__wrapped__",
                write_prefill_scatter),
        (pools, dense, jnp.full((MB,), nb, jnp.int32),
         jnp.zeros((), jnp.int32)),
        donate_argnums=(0,))
    return [prefill, paged, chunk, ragged, chunked_prefill, write_prefill]


def _collective_programs() -> List[_Program]:
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from ..parallel import shard_map
    from ..parallel.ring_attention import (ring_attention,
                                           ulysses_attention)

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(
            "collective registry programs need >= 4 devices; run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 (the "
            "jaxcost CLI and tests/conftest.py both set this)")
    mesh = Mesh(np.asarray(devs[:4]), ("sp",))
    B, H, T, D = 1, 4, 32, 8
    q = jnp.zeros((B, H, T, D), jnp.float32)
    # ptlint: disable=PT-S001  this IS the committed layout: the
    # collective.* registry programs define the byte budget that
    # jaxcost_budget.json and shardplan.json both pin (the jaxshard
    # registry mirrors these literals so the cross-artifact check
    # compares like with like)
    spec = P(None, None, "sp", None)

    ring = shard_map(lambda a, b, c: ring_attention(a, b, c, "sp"),
                     mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
    uly = shard_map(lambda a, b, c: ulysses_attention(a, b, c, "sp"),
                    mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)

    # the grad-all-reduce shape: per-leaf psum over the dp axis (what
    # ShardedTrainStep's gradient sync lowers to)
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
        _Program("collective.ring_attention", ring, (q, q, q),
                 donation_applies=False),
        _Program("collective.ulysses_attention", uly, (q, q, q),
                 donation_applies=False),
        _Program("collective.psum_tree", pt, (tree,),
                 donation_applies=False),
    ]


_GROUPS: Tuple[Tuple[str, Callable], ...] = (
    ("train_step", _train_step_programs),
    ("decode.", _decode_sub_programs),
    ("serving.", _serving_programs),
    ("collective.", _collective_programs),
)

_REGISTRY_NAMES = (
    "train_step",
    "decode.token_embed", "decode.qkv", "decode.cache_write",
    "decode.attn", "decode.head",
    "serving.prefill", "serving.paged_decode", "serving.decode_chunk",
    "serving.ragged_attention", "serving.chunked_prefill",
    "serving.write_prefill",
    "collective.ring_attention", "collective.ulysses_attention",
    "collective.psum_tree",
)


def registry_names() -> List[str]:
    return list(_REGISTRY_NAMES)


def _build_programs(names: Optional[Sequence[str]] = None
                    ) -> List[_Program]:
    if names is not None:
        unknown = sorted(set(names) - set(_REGISTRY_NAMES))
        if unknown:
            raise KeyError(
                f"unknown program(s): {', '.join(unknown)}; known: "
                f"{', '.join(_REGISTRY_NAMES)}")
    wanted = set(names) if names is not None else None
    out: List[_Program] = []
    for prefix, builder in _GROUPS:
        if wanted is not None and not any(n.startswith(prefix)
                                          for n in wanted):
            continue
        for prog in builder():
            if wanted is None or prog.name in wanted:
                out.append(prog)
    return out


def compute_costs(names: Optional[Sequence[str]] = None
                  ) -> Dict[str, ProgramCost]:
    """Static cost of every (selected) registered program."""
    return {p.name: estimate_fn(p.fn, *p.args,
                                static_argnums=p.static_argnums,
                                name=p.name)
            for p in _build_programs(names)}


def collect_donation_findings(names: Optional[Sequence[str]] = None
                              ) -> List[DonationFinding]:
    """Donation audit over every (selected) registered program where
    donation is that program's own decision (skips shard_map'd library
    collectives — their donation belongs to the caller's jit)."""
    findings: List[DonationFinding] = []
    for p in _build_programs(names):
        if not p.donation_applies:
            continue
        findings.extend(audit_donation(
            p.fn, *p.args, name=p.name,
            donate_argnums=p.donate_argnums,
            static_argnums=p.static_argnums, suppress=p.suppress))
    return findings


# ------------------------------------------------------------------- budget
DEFAULT_TOLERANCE = 0.05
BUDGET_METRICS = ("flops", "peak_bytes", "comm_bytes")


def write_budget(path: str, costs: Dict[str, ProgramCost],
                 tolerance: float = DEFAULT_TOLERANCE) -> None:
    payload = {
        "version": 1,
        "tolerance": tolerance,
        "programs": {
            name: {m: getattr(c, m) for m in BUDGET_METRICS}
            for name, c in sorted(costs.items())},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def check_budget(path: str, costs: Dict[str, ProgramCost],
                 require_full_coverage: bool = True) -> List[str]:
    """Violations list (empty = within budget). A program is over
    budget when any metric exceeds its committed value by more than
    the file's tolerance. With `require_full_coverage`, programs
    missing from either side are violations too — a silently dropped
    program is how regressions hide."""
    with open(path) as f:
        payload = json.load(f)
    tol = float(payload.get("tolerance", DEFAULT_TOLERANCE))
    budget = payload.get("programs", {})
    violations: List[str] = []
    for name in sorted(costs):
        ref = budget.get(name)
        if ref is None:
            violations.append(
                f"{name}: not in budget file (intentional new program? "
                f"re-baseline with --budget write)")
            continue
        for metric in BUDGET_METRICS:
            cur = int(getattr(costs[name], metric))
            bud = int(ref.get(metric, 0))
            if cur > bud * (1.0 + tol):
                over = (cur / bud - 1.0) * 100 if bud else float("inf")
                violations.append(
                    f"{name}: {metric} {cur:,} exceeds budget {bud:,} "
                    f"by {over:.1f}% (tolerance {tol:.0%})")
    if require_full_coverage:
        for name in sorted(set(budget) - set(costs)):
            violations.append(
                f"{name}: in budget file but not produced by this run "
                f"(program removed? re-baseline with --budget write)")
    return violations
