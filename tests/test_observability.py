"""Unified telemetry layer (paddle_tpu/obs/) — PR 6 acceptance.

The load-bearing pins:

- histogram quantiles are EXACT (numpy-identical) while the sample
  window holds every observation — the SLO numbers the load suite
  asserts are not bucket interpolations;
- label isolation: two children of one family never share state (two
  engines can run side by side without merging series);
- thread safety: concurrent recording loses nothing;
- exporters round-trip: JSON snapshot, Prometheus text shape
  (cumulative le buckets), chrome trace categories;
- the serving engine records TTFT exactly once per request and its
  cache-block gauges agree with PagedKVCache.check_integrity
  (zero-leak stays a live metric, not just an audit);
- the load suite's steady scenario passes its SLOs in-process (tier-1
  smoke; the full 4-scenario suite is the `slow` lane / BENCH_FULL).
"""
import json
import math
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.obs.registry import MetricRegistry


# ------------------------------------------------------------- registry
def test_counter_monotonic_and_negative_rejected():
    reg = MetricRegistry()
    c = reg.counter("c_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    reg = MetricRegistry()
    g = reg.gauge("g")
    g.set(4)
    g.inc(2)
    g.dec(5)
    assert g.value == 1.0


def test_histogram_quantiles_exact_vs_numpy():
    reg = MetricRegistry()
    h = reg.histogram("h_seconds")
    rng = np.random.RandomState(0)
    xs = rng.lognormal(mean=-4.0, sigma=1.5, size=1000)
    for x in xs:
        h.observe(x)
    for q in (0.0, 0.5, 0.9, 0.99, 1.0):
        assert h.quantile(q) == float(np.quantile(xs, q))
    child = h.labels()
    assert child.count == 1000
    assert child.sum == pytest.approx(float(xs.sum()))
    # cumulative buckets: each le count equals the numpy-side count
    for bound, cum in child.buckets().items():
        assert cum == int((xs <= bound).sum())


def test_histogram_window_rolls_past_cap():
    reg = MetricRegistry()
    h = reg.histogram("h2", sample_cap=100)
    for v in range(200):
        h.observe(float(v))
    child = h.labels()
    assert child.count == 200                 # count/sum exact forever
    assert child.sum == sum(range(200))
    # quantiles cover the latest window only (100..199)
    assert h.quantile(0.0) == 100.0
    assert h.quantile(1.0) == 199.0


def test_histogram_empty_quantile_nan_and_bad_bounds():
    reg = MetricRegistry()
    h = reg.histogram("h3")
    assert math.isnan(h.quantile(0.5))
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        reg.histogram("h4", buckets=(1.0, 0.5))


def test_label_isolation_and_get_never_creates():
    reg = MetricRegistry()
    fam = reg.counter("events_total", labels=("engine", "event"))
    fam.labels(engine="a", event="steps").inc(3)
    fam.labels(engine="b", event="steps").inc(5)
    assert fam.labels(engine="a", event="steps").value == 3
    assert fam.labels(engine="b", event="steps").value == 5
    assert fam.get(engine="c", event="steps") is None
    assert len(fam.children()) == 2           # get() minted nothing
    with pytest.raises(ValueError):
        fam.labels(engine="a")                # missing label name
    with pytest.raises(ValueError):
        fam.inc()                             # labeled family: no proxy


def test_redeclare_idempotent_but_shape_mismatch_raises():
    reg = MetricRegistry()
    a = reg.counter("x_total", labels=("k",))
    assert reg.counter("x_total", labels=("k",)) is a
    with pytest.raises(ValueError):
        reg.gauge("x_total", labels=("k",))
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("other",))


def test_thread_safety_concurrent_recording():
    reg = MetricRegistry()
    c = reg.counter("c_total")
    h = reg.histogram("h_seconds", sample_cap=100_000)
    n_threads, n_iter = 8, 2000

    def work():
        for i in range(n_iter):
            c.inc()
            h.observe(i * 1e-4)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.labels().count == n_threads * n_iter
    assert len(h.labels()._samples) == n_threads * n_iter


# ------------------------------------------------------------- exporters
def _sample_registry():
    reg = MetricRegistry()
    reg.counter("req_total", help="requests", labels=("engine",)) \
       .labels(engine="e0").inc(7)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, float("inf")))
    for v in (0.05, 0.5, 2.0, 0.07):
        h.observe(v)
    return reg


def test_snapshot_json_round_trip(tmp_path):
    reg = _sample_registry()
    p = tmp_path / "snap.json"
    obs.dump_snapshot(str(p), reg)
    snap = json.loads(p.read_text())
    by_name = {m["name"]: m for m in snap["metrics"]}
    assert by_name["req_total"]["series"][0] == {
        "labels": {"engine": "e0"}, "value": 7.0}
    hist = by_name["lat_seconds"]["series"][0]
    assert hist["count"] == 4
    assert hist["buckets"] == {"0.1": 2, "1.0": 3, "+Inf": 4}
    assert hist["p50"] == float(np.quantile([0.05, 0.5, 2.0, 0.07], 0.5))


def test_prometheus_text_shape():
    text = obs.to_prometheus(_sample_registry())
    lines = text.splitlines()
    assert "# TYPE req_total counter" in lines
    assert 'req_total{engine="e0"} 7.0' in lines
    assert "# TYPE lat_seconds histogram" in lines
    # cumulative le buckets ending at +Inf == _count
    assert 'lat_seconds_bucket{le="0.1"} 2' in lines
    assert 'lat_seconds_bucket{le="1.0"} 3' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 4' in lines
    assert "lat_seconds_count 4" in lines
    assert any(line.startswith("lat_seconds_sum ") for line in lines)


def test_snapshot_exporter_writes_file(tmp_path):
    reg = _sample_registry()
    p = tmp_path / "periodic.json"
    with obs.SnapshotExporter(str(p), interval_s=60.0, registry=reg):
        pass                                  # stop() writes a final snap
    snap = json.loads(p.read_text())
    assert any(m["name"] == "req_total" for m in snap["metrics"])


def test_chrome_trace_categories_and_nesting(tmp_path):
    obs.trace.clear()
    obs.trace.enable()
    try:
        with obs.span("outer", cat="checkpoint", annotate=False):
            with obs.span("inner", annotate=False,
                          args={"kind": "full"}):
                pass
    finally:
        obs.trace.disable()
    p = tmp_path / "trace.json"
    obs.export_chrome_trace(str(p))
    evs = json.loads(p.read_text())["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["cat"] == "checkpoint"
    assert by_name["inner"]["cat"] == "op"    # default category
    assert by_name["inner"]["args"] == {"kind": "full"}
    # inner nests inside outer on the timeline
    assert by_name["outer"]["ts"] <= by_name["inner"]["ts"]
    assert (by_name["inner"]["ts"] + by_name["inner"]["dur"]
            <= by_name["outer"]["ts"] + by_name["outer"]["dur"] + 1e-3)
    depths = {e.name: e.depth for e in obs.trace.events()}
    assert depths == {"outer": 0, "inner": 1}


def test_gauge_history_and_chrome_counter_track(tmp_path):
    # every Gauge.set/inc/dec appends to a bounded history ring; the
    # chrome export renders the listed gauge families as ph:"C"
    # counter tracks clipped to the trace window
    reg = MetricRegistry()
    fam = reg.gauge("serving_waiting", labels=("engine",))
    g = fam.labels(engine="e-0")
    g.set(2.0)                               # before enable(): clipped
    obs.trace.clear()
    obs.trace.enable()
    try:
        with obs.span("step", cat="decode", annotate=False):
            g.set(5.0)
            g.inc(1.0)
            g.dec(2.0)
    finally:
        obs.trace.disable()
    assert [v for _, v in g.samples()] == [2.0, 5.0, 6.0, 4.0]
    ts = [t for t, _ in g.samples()]
    assert ts == sorted(ts)

    p = tmp_path / "trace.json"
    obs.export_chrome_trace(str(p), registry=reg)
    evs = json.loads(p.read_text())["traceEvents"]
    counters = [e for e in evs if e["ph"] == "C"]
    assert [e["args"]["value"] for e in counters] == [5.0, 6.0, 4.0]
    assert all(e["name"] == "serving_waiting{engine=e-0}"
               and e["ts"] >= 0 for e in counters)
    # spans still come through alongside the counter track
    assert any(e["ph"] == "X" and e["name"] == "step" for e in evs)

    # history ring is bounded
    from paddle_tpu.obs.registry import GAUGE_HISTORY_CAP
    for i in range(GAUGE_HISTORY_CAP + 10):
        g.set(float(i))
    assert len(g.samples()) == GAUGE_HISTORY_CAP


def test_profiler_shim_shares_trace_table():
    from paddle_tpu import profiler
    assert profiler.RecordEvent is obs.Span
    assert profiler._ProfState is obs.trace._TraceState
    obs.trace.clear()
    obs.trace.enable()
    try:
        with profiler.RecordEvent("legacy", annotate=False):
            pass
    finally:
        obs.trace.disable()
    assert [e.name for e in obs.trace.events()] == ["legacy"]


# ----------------------------------------------------- engine step metrics
def _tiny_engine():
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.inference.serving import EngineConfig, LLMEngine
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=24)
    m = GPT(cfg)
    m.eval()
    ecfg = EngineConfig(block_size=4, num_blocks=16, max_num_seqs=4,
                        obs_label="obs-test")
    return LLMEngine.from_model(m, ecfg)


def test_engine_ttft_once_per_request_and_block_gauges():
    from paddle_tpu.inference.serving import SamplingParams
    eng = _tiny_engine()
    label = eng.stats.label
    n_req = 3
    rng = np.random.RandomState(0)
    for _ in range(n_req):
        eng.add_request(rng.randint(0, 97, (5,), dtype=np.int32),
                        SamplingParams(max_tokens=4))
    eng.run()

    d = eng.stats.as_dict()
    assert d["completed"] == n_req
    # TTFT observed EXACTLY once per request (first token only)
    ttft = obs.REGISTRY.get("serving_ttft_seconds").get(engine=label)
    assert ttft is not None and ttft.count == n_req
    # ... while token gaps cover every later token
    gaps = obs.REGISTRY.get("serving_token_gap_seconds").get(engine=label)
    assert gaps.count == d["generated_tokens"] - n_req
    lat = obs.REGISTRY.get("serving_request_latency_seconds") \
                      .get(engine=label)
    assert lat.count == n_req
    # step histogram: one observation per engine step
    steps = obs.REGISTRY.get("serving_step_seconds").get(engine=label)
    assert steps.count == d["steps"] > 0
    # ttft quantiles read through the stats view, numpy-exact
    assert eng.stats.ttft_quantile(0.5) == ttft.quantile(0.5) > 0

    # zero-leak as a live metric: post-drain the used/free block gauges
    # agree with the cache audit
    integ = eng.cache.check_integrity()
    assert integ["leaked"] == 0
    blocks = obs.REGISTRY.get("serving_cache_blocks")
    assert blocks.get(engine=label, state="used").value \
        == eng.cache.num_used() == 0
    assert blocks.get(engine=label, state="free").value \
        == eng.cache.num_free()
    # queue gauges drained
    assert obs.REGISTRY.get("serving_running").get(engine=label).value == 0
    assert obs.REGISTRY.get("serving_waiting").get(engine=label).value == 0


def test_engine_labels_never_merge():
    eng_a = _tiny_engine()
    eng_b = _tiny_engine()
    assert eng_a.stats.label != eng_b.stats.label
    eng_a.stats.steps += 1
    fam = obs.REGISTRY.get("serving_events_total")
    assert fam.labels(engine=eng_a.stats.label, event="steps").value == 1
    assert fam.labels(engine=eng_b.stats.label, event="steps").value == 0


def test_stats_thin_view_round_trip():
    eng = _tiny_engine()
    s = eng.stats
    s.prefill_tokens += 7
    s.time_decode += 0.25
    assert s.prefill_tokens == 7
    assert s.time_decode == pytest.approx(0.25)
    with pytest.raises(ValueError):
        s.steps -= 1                          # counters never go down
    d = s.as_dict()
    assert d["prefill_tokens"] == 7 and isinstance(d["prefill_tokens"], int)


# ------------------------------------------------------------- load suite
def _load_suite_mod():
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import load_suite
    return load_suite


def test_load_suite_steady_smoke():
    ls = _load_suite_mod()
    m = ls.run_scenario("steady", n=4, fast=True)
    assert m["slo"]["pass"], m["slo"]["violations"]
    assert m["completed"] == m["submitted"] == 4
    assert m["reject_rate"] == 0.0
    assert m["tokens_per_sec"] > 0
    assert 0 < m["ttft_p50"] <= m["ttft_p99"]
    # trace-derived TTFT decomposition rides next to the quantiles
    d = m["ttft_decomposition"]
    assert d["n"] == 4
    for k in ("queue_s", "prefill_s", "first_gap_s"):
        assert d[k] >= 0.0
    # the recorder-overhead A/B is pinned (gate skipped when the
    # host's same-config noise floor drowns it — but always reported)
    assert "recorder_overhead_pct" in m
    assert isinstance(m["recorder_overhead_noisy"], bool)


@pytest.mark.slow
def test_load_suite_full():
    ls = _load_suite_mod()
    report = ls.run_suite(fast=True)
    assert set(report["scenarios"]) == set(ls.SCENARIOS)
    assert report["slo_pass"], {
        k: v["slo"]["violations"] for k, v in report["scenarios"].items()
        if not v["slo"]["pass"]}
    # the chaos scenario actually exercised the fault path
    assert report["scenarios"]["chaos_kill"]["errors"] > 0
