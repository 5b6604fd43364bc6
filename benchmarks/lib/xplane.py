"""From the profiler's trace to device busy time, idle share, the
operations that took most time and the longest idle gaps.

`read` is a thin reader over jax.profiler.ProfileData (nothing but JAX):
it yields (plane, line, name, start_ns, dur_ns). `reduce` works on such
tuples, so that it can be checked on a hand-written list and on the
cut-down recorded traces under benchmarks/fixtures/.

What the v5e's trace looks like (read by hand, PR 26): one plane per chip
named `/device:TPU:<n>`. Its line `XLA Ops` holds one event per executed
HLO operation, named by the operation's whole HLO text (`%copy.2 =
f32[512,32,16,64]{...} copy(...)`; a Mosaic kernel is a `custom-call` whose
name is the kernel's, `%ragged_decode_attention.271 = ...`), with control
flow (`while`, `conditional`) as an event that ENCLOSES the operations of
its body. Its line `XLA Modules` holds one event per executed program
(`jit_prefill(<fingerprint>)`); `Async XLA Ops` repeats the asynchronous
copies and is not read. The host's threads are lines of the plane
`/host:CPU`; the main thread's line holds the benchmark's own spans
(`bench.*`, lib/tracing.py) and JAX's (`PjitFunction(<name>)`, `DevicePut`,
`np.asarray(jax.Array)`), on the same clock as the device.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
TOP = 10
#: host events longer than this are few (the benchmark's spans, a blocking
#: fetch) and are looked at for every gap; the rest are found by bisection
LONG_NS = 10_000_000


def newest(trace_dir: str):
    """Path of the newest .xplane.pb under `trace_dir`, or None."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def read(path: str):
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                yield (plane.name, line.name, ev.name,
                       int(ev.start_ns), int(ev.duration_ns))


_HLO = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = (.+?) ([\w\-]+)\(")


def short_name(hlo: str) -> str:
    """`<name> <opcode> <result type>` of an HLO operation's text, without
    the numeric suffix and the layouts, so that the same operation of every
    layer and every step is one entry: `copy copy f32[512,32,16,64]`."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:100]
    kind = re.sub(r"\{[^}]*\}", "", m.group(2)).replace(" ", "")
    return f"{m.group(1)} {m.group(3)} {kind}"[:100]


def union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def self_times(events):
    """{name: ns} where an event's own time is its duration less the part
    its enclosed events cover (events of one line nest, never cross)."""
    total = {}
    stack = []                      # (end, name, dur, covered by children)

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, dur, covered = stack.pop()
            total[name] = total.get(name, 0) + max(0, dur - covered)

    for s, d, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(s)
        if stack:
            stack[-1][3] += d
        stack.append([s + d, name, d, 0])
    close(float("inf"))
    return total


def reduce(events, chips: int = 1):
    """events: iterable of (plane, line, name, start_ns, dur_ns). Returns
    {"busy_s", "window_s", "modules", "breakdown"} averaged over the first
    `chips` device planes, or None where no operation ran on a device. The
    window is the span of the benchmark's `bench.window` host span where
    the trace has one, else first device operation to last. `device_ops`
    are the operations with most OWN time (an enclosing `while` is not
    charged for its body), by `short_name`; `idle_gaps` are the idle
    seconds summed by what the host was doing in each gap (`host_doing`);
    `modules` are the programs executed: [name, executions, seconds over
    all chips]."""
    by_plane, host, window, modules = {}, {}, None, {}
    for plane, line, name, s, d in events:
        if plane.startswith(DEVICE_PLANE) and line == OPS_LINE:
            by_plane.setdefault(plane, []).append((s, d, short_name(name)))
        elif plane.startswith(DEVICE_PLANE) and line == MODULES_LINE:
            key = name.split("(")[0]
            count, ns = modules.get(key, (0, 0))
            modules[key] = (count + 1, ns + d)
        elif plane == HOST_PLANE:
            if name == SPAN_PREFIX + "window":
                window = (s, s + d)
            else:
                host.setdefault(line, []).append((s, s + d, name))
    planes = sorted(by_plane)[:chips]
    if not planes:
        return None
    # the thread that drives the program is the one with the benchmark's
    # spans; JAX's own host events of that thread say what it dispatched
    spans = [e for evs in host.values()
             if any(n.startswith(SPAN_PREFIX) for _, _, n in evs)
             for e in evs]
    busy, span_ns, ops, gaps = 0, 0, {}, []
    for p in planes:
        evs = by_plane[p]
        lo = min(s for s, _, _ in evs)
        hi = max(s + d for s, d, _ in evs)
        if window is not None:
            lo, hi = window
        merged = [(max(s, lo), min(e, hi)) for s, e in
                  union((s, s + d) for s, d, _ in evs) if e > lo and s < hi]
        busy += sum(e - s for s, e in merged)
        span_ns += hi - lo
        for name, ns in self_times(evs).items():
            ops[name] = ops.get(name, 0) + ns
        edges = [lo] + [t for se in merged for t in se] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(planes)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    idle = {}
    short = sorted(e for e in spans if e[1] - e[0] <= LONG_NS)
    long_ = [e for e in spans if e[1] - e[0] > LONG_NS]
    starts = [s for s, _, _ in short]
    for g in gaps:
        near = short[bisect.bisect_left(starts, g[0] - LONG_NS):
                     bisect.bisect_right(starts, g[1])]
        doing = host_doing(g, near + long_)
        idle[doing] = idle.get(doing, 0) + g[1] - g[0]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy / n / 1e9, "window_s": span_ns / n / 1e9,
        "modules": [[k, c, ns / 1e9] for k, (c, ns) in sorted(
            modules.items(), key=lambda kv: -kv[1][1])[:TOP]],
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
            "idle_gaps": [[k, v / n / 1e9] for k, v in top_idle]}}


def host_doing(gap, spans) -> str:
    """What the driving thread was in during the gap. In this order: the
    shortest of JAX's host events (`PjitFunction(...)`, `DevicePut`, ...)
    that covers half of the gap; JAX's event that covers most of it, if a
    fifth or more; the shortest `bench.*` span that covers half of it;
    `unattributed`."""
    length = gap[1] - gap[0]
    best, best_key = "unattributed", None
    for s, e, name in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        ours = name.startswith(SPAN_PREFIX)
        if not ours and 2 * cover >= length:
            key = (0, e - s)
        elif not ours and 5 * cover >= length:
            key = (1, -cover)
        elif ours and 2 * cover >= length:
            key = (2, e - s)
        else:
            continue
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best


def reduce_dir(trace_dir: str, chips: int = 1):
    path = newest(trace_dir)
    return reduce(read(path), chips) if path else None
