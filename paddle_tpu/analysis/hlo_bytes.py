"""HLO-text byte accounting — the one source of truth.

Stdlib-only (no jax import) so CLI wrappers can parse dumps without
initializing a backend. Three entry points:

- `shape_bytes(text)`: bytes of every HLO shape literal in a string
  (`f32[8,128]` -> 4096); tuples and layout `{...}` blocks tolerated;
- `audit_text(text, top_n)`: rank an optimized-HLO ENTRY computation's
  instructions by first-order HBM traffic (output + operand bytes;
  fusion internals intentionally uncounted — they live in VMEM);
- `allreduce_payload(hlo)`: total payload bytes and op count over
  `all-reduce` / `all-reduce-start` defining lines of a partitioned
  module (the per-device wire volume of a sharded step).

tools/hlo_bytes.py is a thin CLI wrapper over this module, and
analysis/jaxcost.py re-exports `shape_bytes` so jaxpr-level and
HLO-level byte accounting share one dtype table.
"""
from __future__ import annotations

import re
from collections import defaultdict

__all__ = ["DTYPE_BYTES", "shape_bytes", "audit_text",
           "allreduce_payload"]

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
# "  %name = <type> <opkind>(operands...), attrs"  — type may contain
# tuple parens and {layout} blocks; opkind is a bare lowercase word with
# optional dashes directly before the operand paren.
_INSTR_RE = re.compile(r"^\s+(%[\w.-]+)\s*=\s*(.*?)\s([a-z][a-z0-9-]*)\(")
_OPERAND_RE = re.compile(r"%[\w.-]+")


def shape_bytes(text: str) -> int:
    """Sum bytes over every HLO shape literal found in `text`."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        b = DTYPE_BYTES.get(dt)
        if b is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * b
    return total


def allreduce_payload(hlo: str):
    """(payload_bytes, op_count) over all-reduce ops in partitioned HLO.

    Shapes appear as `f32[1576960]{0} all-reduce(` or, for multi-operand
    ops, `(f32[8], f32[16384]) all-reduce(`. Counts each op once (the
    defining line, not operand uses).
    """
    total, count = 0, 0
    for line in hlo.splitlines():
        m = re.search(r"=\s+(\([^)]*\)|\S+)\s+all-reduce(?:-start)?\(",
                      line)
        if not m:
            continue
        count += 1
        total += shape_bytes(m.group(1))
    return total, count


def audit_text(text: str, top_n: int = 30):
    """Rank ENTRY instructions of an optimized-HLO dump by bytes touched
    (output + named operands). Prints a report; returns the rows."""
    i = text.index("\nENTRY ")
    entry = text[i + 1:]
    entry = entry[:entry.index("\n}")]
    lines = entry.splitlines()
    # entry params: name: type pairs in the header (may span the one line)
    out_bytes = {}
    header = lines[0]
    for m in re.finditer(r"(%?[\w.-]+):\s*((?:\([^)]*\)|[a-z]+\d*\[[\d,]*\])"
                         r"(?:\{[^}]*\})?)", header):
        out_bytes["%" + m.group(1).lstrip("%")] = shape_bytes(m.group(2))
    rows = []
    for line in lines[1:]:
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, out_type, kind = m.groups()
        ob = shape_bytes(out_type)
        out_bytes[name] = ob
        # operand list: inside the first top-level paren after kind
        args_start = line.index(kind + "(") + len(kind)
        depth = 0
        j = args_start
        for j in range(args_start, len(line)):
            if line[j] == "(":
                depth += 1
            elif line[j] == ")":
                depth -= 1
                if depth == 0:
                    break
        args = line[args_start:j + 1]
        ab = sum(out_bytes.get(op, 0) for op in _OPERAND_RE.findall(args))
        rows.append((ob + ab, ob, ab, kind, name, line.strip()[:180]))
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    print(f"total touched (first-order): {total/1e9:.2f} GB over "
          f"{len(rows)} instructions")
    by_kind = defaultdict(float)
    for tb, ob, ab, kind, name, _ in rows:
        by_kind[kind] += tb
    print("\n== bytes by op kind ==")
    for kind, b in sorted(by_kind.items(), key=lambda kv: -kv[1])[:15]:
        print(f"{b/1e9:8.2f} GB  {kind}")
    print(f"\n== top {top_n} instructions ==")
    print(f"{'MB':>9} {'outMB':>8} {'kind':<14} name")
    for tb, ob, ab, kind, name, line in rows[:top_n]:
        print(f"{tb/1e6:9.1f} {ob/1e6:8.1f} {kind:<14} {name[:60]}")
    # f32 big-tensor check: any instruction producing a large fp32 output
    big_f32 = [(ob, name, line) for tb, ob, ab, kind, name, line in rows
               if ob > 40e6 and re.search(r"\bf32\[", line.split(" = ")[1]
                                          if " = " in line else line)]
    print(f"\n== >40MB fp32 outputs: {len(big_f32)} ==")
    for ob, name, line in big_f32[:15]:
        print(f"{ob/1e6:9.1f} {name[:60]}")
    return rows
