"""The device trace of a few seconds of the window, and the benchmark's own
host spans on the same clock (`span`: a jax.profiler.TraceAnnotation, which
costs nothing when no trace is being taken)."""
from __future__ import annotations

import contextlib
import shutil

import jax

span = jax.profiler.TraceAnnotation


@contextlib.contextmanager
def device_trace(trace_dir: str):
    """Trace the block into `trace_dir` (emptied first). No Python call
    stacks and no HLO: the file that comes back from the chip is capped."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
