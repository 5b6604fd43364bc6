"""The program's own spans (`serving.*`, paddle_tpu.obs.Span ->
jax.profiler.TraceAnnotation) read beside the device's operations, on the
profiler's one clock.

`lib/xplane.py` reduces a trace to busy time and a top ten and treats every
host event that is not `bench.*` as JAX's. This reader keeps what that one
drops: the spans' names, their nesting and their stats (`request_id`,
`context_tokens`), and each device operation's HLO text. It answers four
questions inside the `bench.window` span of a trace:

(a) `idle_by_span`: the device's idle intervals, each cut at span borders
    and charged to the INNERMOST program span of the driving thread that
    covers the piece, else to `outside`. The pieces of a gap add up to the
    gap, so the charges add up to window - busy, what
    `device_idle_share.*` reads from the same trace;
(b) `under`: per span of a name, its host seconds and the device-busy
    seconds inside it;
(c) `kernel_ops`: the device operations of a kernel, by the stable name the
    program gave it (`pl.pallas_call(name=...)`);
(d) `joined`: pairs of spans that share a stat (`request_id`).

`Trace` takes (plane, line, name, start_ns, dur_ns, stats) tuples, so that
it can be checked on a hand-written list and on the cut-down recorded
slices under benchmarks/fixtures/spans/. A trace with no device plane (a
CPU rehearsal) gives None everywhere, and so does a program without spans
(the parent of the PR that added them): the metric is then left out.
"""
from __future__ import annotations

import json
import re
from typing import NamedTuple, Optional

from lib import xplane

PROGRAM_PREFIX = "serving."
WINDOW = xplane.SPAN_PREFIX + "window"
OUTSIDE = "outside"
#: JAX's transforms, whose names XLA puts before a kernel's own
TRANSFORMS = ("jvp", "transpose", "vmap", "remat", "checkpoint")


class Span(NamedTuple):
    name: str
    start: int
    end: int
    stats: dict


def read(path: str):
    """Like xplane.read, with a sixth field: the stats of the program's and
    the benchmark's host spans ({} for every other event)."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        host = plane.name == xplane.HOST_PLANE
        for line in plane.lines:
            for ev in line.events:
                ours = host and ev.name.startswith(
                    (PROGRAM_PREFIX, xplane.SPAN_PREFIX))
                yield (plane.name, line.name, ev.name, int(ev.start_ns),
                       int(ev.duration_ns), dict(ev.stats) if ours else {})


class Trace:
    """One chip's operations and the driving thread's program spans, cut to
    the window. `ops` and `modules` are (start, end, text) sorted by start,
    `busy` the merged busy intervals, `spans` the program's spans sorted by
    (start, longest first)."""

    def __init__(self, events):
        ops, modules, host, window = {}, {}, {}, None
        for plane, line, name, s, d, stats in events:
            if plane.startswith(xplane.DEVICE_PLANE):
                if line == xplane.OPS_LINE:
                    ops.setdefault(plane, []).append((s, s + d, name))
                elif line == xplane.MODULES_LINE:
                    modules.setdefault(plane, []).append((s, s + d, name))
            elif plane == xplane.HOST_PLANE:
                if name == WINDOW:
                    window = (s, s + d, line)
                elif name.startswith(PROGRAM_PREFIX):
                    host.setdefault(line, []).append(
                        Span(name, s, s + d, stats))
        chip = min(ops, default=None)           # one chip: the first plane
        self.ok = chip is not None
        if not self.ok:
            return
        self.ops = sorted(ops[chip])
        self.modules = sorted(modules.get(chip, []))
        if window is None:
            # someone else's trace: first operation to last, and the thread
            # with most of the program's spans
            self.lo = self.ops[0][0]
            self.hi = max(e for _, e, _ in self.ops)
            thread = max(host, key=lambda k: len(host[k]), default=None)
        else:
            self.lo, self.hi, thread = window
        self.busy = [(max(s, self.lo), min(e, self.hi)) for s, e in
                     xplane.union((s, e) for s, e, _ in self.ops)
                     if e > self.lo and s < self.hi]
        self.spans = sorted(host.get(thread, []),
                            key=lambda sp: (sp.start, -sp.end))
        self.kernels = {}               # kernel name -> its operations

    # ----------------------------------------------------------- pieces
    def inside(self, start: int, end: int) -> bool:
        return self.lo <= start and end <= self.hi

    def busy_ns(self, start: int, end: int) -> int:
        return sum(min(e, end) - max(s, start) for s, e in self.busy
                   if e > start and s < end)

    def innermost(self):
        """[(start, end, name)]: the window cut at every span border, each
        piece named by the innermost span that covers it, else OUTSIDE."""
        edges = []                      # (time, 0 close / 1 open, span)
        for sp in self.spans:
            s, e = max(sp.start, self.lo), min(sp.end, self.hi)
            if e > s:
                edges += [(s, 1, sp), (e, 0, sp)]
        edges.sort(key=lambda t: (t[0], t[1], -t[2].end if t[1] else 0))
        pieces, stack, at = [], [], self.lo
        for t, opens, sp in edges:
            if t > at:
                pieces.append((at, t, stack[-1].name if stack else OUTSIDE))
                at = t
            if opens:
                stack.append(sp)
            else:
                stack.remove(sp)
        if self.hi > at:
            pieces.append((at, self.hi, OUTSIDE))
        return pieces


def load(record) -> Optional[Trace]:
    """The Trace of the run's newest xplane file; None where the run took no
    trace or no operation ran on a device. Read once a run: it is kept in
    the record, which every layer metric of the run is handed."""
    if "_spans" not in record:
        path = xplane.newest(record.get("trace_dir") or "")
        record["_spans"] = Trace(read(path)) if path else None
    trace = record["_spans"]
    return trace if trace is not None and trace.ok else None


# ------------------------------------------------------------ (a) idle
def idle_by_span(trace: Trace) -> dict:
    """{span name or OUTSIDE: idle ns}; the values add up to window - busy."""
    gaps, at = [], trace.lo
    for s, e in trace.busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if trace.hi > at:
        gaps.append((at, trace.hi))
    out, pieces, i = {}, trace.innermost(), 0
    for g0, g1 in gaps:
        while pieces[i][1] <= g0:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < g1:
            p0, p1, name = pieces[j]
            out[name] = out.get(name, 0) + min(p1, g1) - max(p0, g0)
            j += 1
    return out


def group_of(name: str) -> str:
    """One of the four groups the serving cell reports: `prefill` and
    `decode` (the phase's span and its children), OUTSIDE, and
    `engine_other` for every other span of the program."""
    for group in ("prefill", "decode"):
        phase = PROGRAM_PREFIX + group
        if name == phase or name.startswith(phase + "."):
            return group
    return OUTSIDE if name == OUTSIDE else "engine_other"


def idle_pct(record, group: str, tell: bool = False):
    """Share of the window (%) that the device idled under the spans of
    `group`; None without a device trace or without program spans."""
    trace = load(record)
    if trace is None or not trace.spans:
        return None
    table = idle_by_span(trace)
    window = trace.hi - trace.lo
    if tell:
        print(json.dumps({"idle_seconds_by_innermost_span": {
            k: v / 1e9 for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])},
            "window_s": window / 1e9}), flush=True)
    return 100.0 * sum(v for k, v in table.items()
                       if group_of(k) == group) / window


# ------------------------------------------------ (b) under a span name
def under(trace: Trace, name: str):
    """[(span, host ns, device-busy ns)] for the spans of that name that
    lie wholly in the window."""
    return [(sp, sp.end - sp.start, trace.busy_ns(sp.start, sp.end))
            for sp in trace.spans
            if sp.name == name and trace.inside(sp.start, sp.end)]


def ms_per_span(record, name: str, device: bool):
    """Mean host ms (or device-busy ms) per span of that name; None where
    the trace holds none."""
    trace = load(record)
    found = under(trace, name) if trace else []
    if not found:
        return None
    return 1e-6 * sum(f[2 if device else 1] for f in found) / len(found)


# ------------------------------------------------------ (c) kernel time
def kernel_ops(trace: Trace, kernel: str, start=None, end=None):
    """[(start, end, hlo text)] of the operations inside [start, end]
    (default: the window) whose HLO name is the kernel's: `kernel...` or
    `<transform>_kernel...`, as JAX names a kernel called under jvp or
    transpose (`jvp(packed_flash_fwd)` becomes `jvp_packed_flash_fwd_`).
    The trace is searched once a kernel."""
    if kernel not in trace.kernels:
        named = re.compile(r"^%?(?:(?:" + "|".join(TRANSFORMS) + r")_)*"
                           + re.escape(kernel))
        trace.kernels[kernel] = [op for op in trace.ops if named.match(op[2])]
    start = trace.lo if start is None else start
    end = trace.hi if end is None else end
    return [op for op in trace.kernels[kernel]
            if op[0] >= start and op[1] <= end]


def kernel_by_span(record, kernel: str, name: str):
    """[(span, [the kernel's operations inside it])] for the spans of that
    name that lie wholly in the window and ran the kernel; [] without a
    trace, spans or kernel."""
    trace = load(record)
    found = [(sp, kernel_ops(trace, kernel, sp.start, sp.end))
             for sp, _, _ in (under(trace, name) if trace else [])]
    return [(sp, ops) for sp, ops in found if ops]


def kernel_by_program(record, kernel: str):
    """[[the kernel's operations]] per program execution (an event of the
    XLA Modules line) that lies wholly in the window and ran the kernel."""
    trace = load(record)
    found = [kernel_ops(trace, kernel, s, e) for s, e, _ in trace.modules
             if trace.inside(s, e)] if trace else []
    return [ops for ops in found if ops]


def seconds(ops) -> float:
    return sum(e - s for s, e, _ in ops) / 1e9


def shapes(hlo: str):
    """[(dtype, dims, bytes per element)] of the arrays an HLO operation's
    text names, the result's first: `%k.1 = bf16[24,6,1024,128]{..}
    custom-call(f32[512,32,16,64]{..} %p)` -> [("bf16", [24, 6, 1024,
    128], 2), ("f32", [512, 32, 16, 64], 4)]."""
    return [(dtype, [int(n) for n in dims.split(",") if n],
             int(re.search(r"\d+", dtype).group()) // 8)
            for dtype, dims in re.findall(r"\b([a-z]+\d+\w*)\[([\d,]*)\]",
                                          hlo.split(" = ", 1)[-1])]


# ---------------------------------------------------- (d) joined spans
def joined(trace: Trace, first: str, then: str, stat: str):
    """[(a, b)]: each span `a` named `first` that starts in the window,
    with the earliest span `b` named `then` that starts after it and has
    the same value of `stat`."""
    later = {}
    for sp in reversed(trace.spans):
        if sp.name == then and stat in sp.stats:
            later.setdefault(sp.stats[stat], []).append(sp)
    pairs = []
    for a in trace.spans:
        if a.name != first or not trace.lo <= a.start < trace.hi:
            continue
        b = next((b for b in reversed(later.get(a.stats.get(stat), []))
                  if b.start >= a.start), None)
        if b is not None:
            pairs.append((a, b))
    return pairs
