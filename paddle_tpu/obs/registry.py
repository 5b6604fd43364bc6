"""Metrics registry: process-wide counters, gauges and histograms.

The system-metrics half of the unified telemetry layer (docs/
observability.md). Every hot path in the repo — LLMEngine.step, the
scheduler, jit.TrainStep, the checkpoint manager, the elastic
supervisor — records into ONE registry through labeled metric families,
so the load suite, the chaos runner and the benchmark all read the same
numbers the same way instead of each keeping private accumulator dicts
(the pre-PR-6 state: EngineStats, profiler tables and bench-local
timers that could silently disagree).

Design (the Prometheus client-library shape, host-side only):

- a Family is a named metric of one kind (counter | gauge | histogram)
  with a fixed tuple of label names; `family.labels(engine="eng0")`
  returns the child time series for those label values, creating it on
  first use. A label-less family IS its own single child.
- Counter: monotonic float (`inc`).  Gauge: settable float
  (`set`/`inc`/`dec`).  Histogram: fixed cumulative buckets (the
  Prometheus export shape) PLUS a bounded window of raw samples so
  `quantile(q)` is EXACT (numpy-identical) while the window holds every
  observation — `tests/test_observability.py` pins this against
  np.quantile. Past `sample_cap` observations the quantiles cover the
  most recent window (count/sum/buckets stay exact forever).
- thread safety: one RLock per registry, shared by its families and
  children; the `_GUARDED_BY` contracts below are enforced lexically by
  ptlint PT-C001. Everything here is host arithmetic on
  already-fetched values — recording NEVER touches the device (PT-T007
  stays clean by construction).

The module is stdlib+numpy only: importing paddle_tpu.obs must not pull
in jax (tools/ptlint.py parity — analysis and telemetry both load
anywhere).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Counter", "Gauge", "Histogram", "Family", "MetricRegistry",
           "REGISTRY", "DEFAULT_BUCKETS"]

# Latency-oriented default buckets (seconds): 0.5ms .. 60s, roughly
# exponential — wide enough for CPU-smoke TTFTs and TPU decode steps.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
    0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, float("inf"))

#: raw-sample window per histogram child; quantiles are numpy-exact
#: while total observations <= this cap (docs/observability.md)
DEFAULT_SAMPLE_CAP = 8192


class Counter:
    """Monotonic counter child. `inc` only goes up — a negative delta
    raises, which is what keeps the EngineStats thin-view honest (its
    setter computes deltas; a decrease would mean the view and the
    registry disagree)."""

    _GUARDED_BY = {"_value": "_lock"}

    def __init__(self, lock: threading.RLock):
        self._lock = lock
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError(f"counter can only increase (inc({n}))")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


#: bounded per-gauge (ts, value) history window backing the chrome-trace
#: counter tracks (obs/export.py); host-cheap: one deque append per set
GAUGE_HISTORY_CAP = 512


class Gauge:
    """Point-in-time value child (queue depth, free blocks, tokens/s).

    Every mutation also appends a (perf_counter, value) sample to a
    bounded history ring so the chrome-trace export can render gauge
    families as Perfetto counter tracks (pool pressure, queue depth)
    alongside the span and per-request tracks."""

    _GUARDED_BY = {"_value": "_lock", "_history": "_lock"}

    def __init__(self, lock: threading.RLock):
        self._lock = lock
        self._value = 0.0
        self._history: deque = deque(maxlen=GAUGE_HISTORY_CAP)

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)
            self._history.append((time.perf_counter(), self._value))

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n
            self._history.append((time.perf_counter(), self._value))

    def dec(self, n: float = 1.0) -> None:
        with self._lock:
            self._value -= n
            self._history.append((time.perf_counter(), self._value))

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def samples(self) -> List[Tuple[float, float]]:
        """The bounded (perf_counter ts, value) history window."""
        with self._lock:
            return list(self._history)


def _norm_bounds(buckets: Sequence[float]) -> Tuple[float, ...]:
    """Validated histogram upper bounds: ascending, +inf-terminated.
    Shared by Histogram and the registry's declare path so a bad bucket
    spec raises at declaration, not at first child creation."""
    bounds = tuple(float(b) for b in buckets)
    if not bounds or bounds[-1] != float("inf"):
        bounds = bounds + (float("inf"),)
    if list(bounds) != sorted(bounds):
        raise ValueError(f"bucket bounds must ascend: {bounds}")
    return bounds


class Histogram:
    """Fixed-bucket histogram child with an exact-quantile sample window.

    `buckets` are upper bounds (le); the last bound must be +inf. The
    cumulative bucket counts are the Prometheus export shape; the raw
    sample window backs `quantile()` with numpy-exact answers while
    `count <= sample_cap` (after that: quantiles of the latest window)."""

    _GUARDED_BY = {"_count": "_lock", "_sum": "_lock",
                   "_bucket_counts": "_lock", "_samples": "_lock",
                   "_next": "_lock"}

    def __init__(self, lock: threading.RLock,
                 buckets: Sequence[float] = DEFAULT_BUCKETS,
                 sample_cap: int = DEFAULT_SAMPLE_CAP):
        bounds = _norm_bounds(buckets)
        if sample_cap < 1:
            raise ValueError("sample_cap must be >= 1")
        self.bounds = bounds
        self.sample_cap = int(sample_cap)
        self._lock = lock
        self._count = 0
        self._sum = 0.0
        self._bucket_counts = [0] * len(bounds)
        self._samples: List[float] = []
        self._next = 0                       # ring write index once full

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self._count += 1
            self._sum += v
            # first bucket whose bound holds v (bounds ascend, last=inf)
            for i, b in enumerate(self.bounds):
                if v <= b:
                    self._bucket_counts[i] += 1
                    break
            if len(self._samples) < self.sample_cap:
                self._samples.append(v)
            else:
                self._samples[self._next] = v
                self._next = (self._next + 1) % self.sample_cap

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def buckets(self) -> Dict[float, int]:
        """Cumulative counts per upper bound (Prometheus `le` shape)."""
        with self._lock:
            out, acc = {}, 0
            for b, c in zip(self.bounds, self._bucket_counts):
                acc += c
                out[b] = acc
            return out

    def quantile(self, q: float) -> float:
        """Exact quantile (numpy linear interpolation) over the retained
        sample window; NaN with no samples."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q must be in [0, 1], got {q}")
        with self._lock:
            if not self._samples:
                return float("nan")
            arr = np.asarray(self._samples, np.float64)
        return float(np.quantile(arr, q))

    def percentiles(self, qs: Iterable[float] = (0.5, 0.9, 0.99)
                    ) -> Dict[str, float]:
        """{'p50': ..., 'p90': ..., 'p99': ...} convenience view."""
        return {f"p{q * 100:g}": self.quantile(q) for q in qs}


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class Family:
    """One named metric family: kind + label names + children per label
    values. A label-less family proxies record calls to its single
    implicit child so `obs.counter("x").inc()` just works."""

    _GUARDED_BY = {"_children": "_lock"}

    def __init__(self, name: str, kind: str, help: str = "",
                 labels: Sequence[str] = (), unit: str = "",
                 lock: Optional[threading.RLock] = None, **child_kw):
        if kind not in _KINDS:
            raise ValueError(f"unknown metric kind {kind!r}")
        self.name = name
        self.kind = kind
        self.help = help
        self.unit = unit
        self.label_names = tuple(labels)
        self._child_kw = child_kw
        self._lock = lock or threading.RLock()
        self._children: Dict[Tuple[str, ...], object] = {}

    def labels(self, **kv) -> object:
        """Child for these label values (created on first use)."""
        if set(kv) != set(self.label_names):
            raise ValueError(
                f"{self.name}: expected labels {self.label_names}, "
                f"got {tuple(sorted(kv))}")
        key = tuple(str(kv[n]) for n in self.label_names)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = _KINDS[self.kind](self._lock, **self._child_kw)
                self._children[key] = child
            return child

    def get(self, **kv) -> Optional[object]:
        """Existing child or None — never creates (exporters and
        read-only callers use this so reads don't mint empty series)."""
        key = tuple(str(kv.get(n, "")) for n in self.label_names)
        with self._lock:
            return self._children.get(key)

    def children(self) -> List[Tuple[Dict[str, str], object]]:
        with self._lock:
            items = list(self._children.items())
        return [(dict(zip(self.label_names, key)), child)
                for key, child in items]

    # ------------------------------------------------- label-less proxy
    def _default(self):
        if self.label_names:
            raise ValueError(
                f"{self.name} has labels {self.label_names}; call "
                f".labels(...) first")
        return self.labels()

    def inc(self, n: float = 1.0) -> None:
        self._default().inc(n)

    def dec(self, n: float = 1.0) -> None:
        self._default().dec(n)

    def set(self, v: float) -> None:
        self._default().set(v)

    def observe(self, v: float) -> None:
        self._default().observe(v)

    @property
    def value(self) -> float:
        return self._default().value

    def quantile(self, q: float) -> float:
        return self._default().quantile(q)


class MetricRegistry:
    """Process-wide family table. `counter`/`gauge`/`histogram` are
    idempotent get-or-create: re-declaring an existing name returns the
    same family (so instrument sites in different modules can declare
    independently) but a kind or label-name mismatch raises — two call
    sites silently recording into differently-shaped series is exactly
    the sink divergence this layer exists to end."""

    _GUARDED_BY = {"_families": "_lock"}

    def __init__(self):
        self._lock = threading.RLock()
        self._families: Dict[str, Family] = {}

    def _declare(self, name: str, kind: str, help: str, labels, unit: str,
                 **child_kw) -> Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != kind or fam.label_names != tuple(labels):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{fam.kind}{fam.label_names}, re-declared as "
                        f"{kind}{tuple(labels)}")
                return fam
            fam = Family(name, kind, help=help, labels=labels, unit=unit,
                         lock=self._lock, **child_kw)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "", labels: Sequence[str] = (),
                unit: str = "") -> Family:
        return self._declare(name, "counter", help, labels, unit)

    def gauge(self, name: str, help: str = "", labels: Sequence[str] = (),
              unit: str = "") -> Family:
        return self._declare(name, "gauge", help, labels, unit)

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = (), unit: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS,
                  sample_cap: int = DEFAULT_SAMPLE_CAP) -> Family:
        return self._declare(name, "histogram", help, labels, unit,
                             buckets=_norm_bounds(buckets),
                             sample_cap=sample_cap)

    def get(self, name: str) -> Optional[Family]:
        with self._lock:
            return self._families.get(name)

    def families(self) -> List[Family]:
        with self._lock:
            return list(self._families.values())

    def reset(self) -> None:
        """Drop every family (tests / scenario isolation). Instrument
        sites keep Family references, so they re-declare on next use —
        safe only between runs, not under concurrent recording."""
        with self._lock:
            self._families.clear()

    def collect(self) -> List[dict]:
        """Plain-data snapshot of every family (export.py serializes
        this as the JSON artifact and the Prometheus text page)."""
        out: List[dict] = []
        for fam in self.families():
            series = []
            for lbls, child in fam.children():
                if fam.kind == "histogram":
                    series.append({
                        "labels": lbls,
                        "count": child.count,
                        "sum": child.sum,
                        "buckets": {("+Inf" if b == float("inf")
                                     else repr(b)): c
                                    for b, c in child.buckets().items()},
                        "p50": child.quantile(0.5),
                        "p90": child.quantile(0.9),
                        "p99": child.quantile(0.99),
                    })
                else:
                    series.append({"labels": lbls, "value": child.value})
            out.append({"name": fam.name, "type": fam.kind,
                        "help": fam.help, "unit": fam.unit,
                        "labels": list(fam.label_names),
                        "series": series})
        return out


#: the process-wide default registry every instrument site records into
REGISTRY = MetricRegistry()
