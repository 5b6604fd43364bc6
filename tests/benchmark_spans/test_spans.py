"""The readers of the program's spans (benchmarks/lib/spans.py and the
layer metrics built on it), beside the benchmark's own frozen tests: on a
hand-written event list, where every number can be checked by eye, and on
one cut-down recorded slice per cell from a chip run
(benchmarks/fixtures/spans/)."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))

from lib import spans, xplane  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
RAGGED = ("%%ragged_decode_attention.%d = f32[2,4,8]{2,1,0:T(8,128)} "
          "custom-call(s32[2,8]{1,0} %%x, s32[2]{0} %%y, f32[2,4,8]{2,1,0} "
          "%%q, f32[16,4,4,8]{3,2,1,0:T(8,128)} %%k, f32[16,4,4,8]{3,2,1,0} %%v), "
          "custom_call_target=\"tpu_custom_call\"")


def ev(plane, line, name, start, end, **stats):
    return (plane, line, name, start, end - start, stats)


#: a window of 1000 ns: one add_request, one engine step with a prefill
#: and a decode chunk, four device operations (busy 300 ns, idle 700 ns)
SERVE = [
    ev(HOST, "python3", "bench.window", 0, 1000),
    ev(HOST, "python3", "bench.resubmit", 5, 40),
    ev(HOST, "python3", "serving.add_request", 10, 30, request_id="a",
       prompt_tokens=7),
    ev(HOST, "python3", "bench.engine_step", 45, 905),
    ev(HOST, "python3", "serving.engine_step", 50, 900, step=3),
    ev(HOST, "python3", "serving.schedule", 55, 60),
    ev(HOST, "python3", "serving.prefill", 60, 260, request_id="a", tokens=7),
    ev(HOST, "python3", "serving.prefill.forward", 60, 90),
    ev(HOST, "python3", "serving.prefill.write_cache", 90, 240, blocks=2),
    ev(HOST, "python3", "serving.prefill.fetch", 240, 250),
    ev(HOST, "python3", "serving.prefill.sample", 250, 258),
    ev(HOST, "python3", "serving.decode", 280, 800, num_seqs=2, chunk=1,
       context_tokens=10),
    ev(HOST, "python3", "serving.decode.pack", 280, 290),
    ev(HOST, "python3", "serving.decode.dispatch", 290, 310),
    ev(HOST, "python3", "serving.decode.fetch", 310, 760),
    ev(HOST, "python3", "serving.decode.drain", 760, 800),
    # JAX's own events and another thread's spans are not the program's
    ev(HOST, "python3", "PjitFunction(scatter)", 100, 130),
    ev(HOST, "worker", "serving.prefill", 0, 1000, request_id="z"),
    # added in the window, prefilled after it
    ev(HOST, "python3", "serving.add_request", 940, 950, request_id="b",
       prompt_tokens=3),
    ev(DEV, "XLA Modules", "jit_fused_decode_chunk(77)", 300, 760),
    ev(DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 100, 200),
    ev(DEV, "XLA Ops", RAGGED % 5, 300, 400),
    ev(DEV, "XLA Ops", RAGGED % 6, 450, 500),
    ev(DEV, "XLA Ops", "%copy.9 = f32[8]{0} copy(f32[8]{0} %q)", 700, 750),
]
IDLE = {  # by innermost span, counted by hand from the list above
    "outside": 10 + 20 + 40 + 50, "serving.add_request": 20 + 10,
    "serving.engine_step": 5 + 20 + 100, "serving.schedule": 5,
    "serving.prefill.forward": 30, "serving.prefill.write_cache": 10 + 40,
    "serving.prefill.fetch": 10, "serving.prefill.sample": 8,
    "serving.prefill": 2, "serving.decode.pack": 10,
    "serving.decode.dispatch": 10, "serving.decode.fetch": 200 + 50 + 10,
    "serving.decode.drain": 40}

FWD = ("%%jvp_packed_flash_fwd_.%d = bf16[2,3,128,128]{3,2,1,0:T(8,128)(2,1)}"
       " custom-call(bf16[2,3,128,128]{3,2,1,0} %%q)")
BWD = ("%%transpose_jvp_packed_flash_bwd__.%d = (bf16[2,3,128,128]{3,2,1,0}, "
       "bf16[2,3,128,128]{3,2,1,0}, bf16[2,3,128,128]{3,2,1,0}) custom-call(")
#: two whole executions of a step and one cut by the window's end
TRAIN = [
    ev(HOST, "python3", "bench.window", 0, 100_000),
    ev(DEV, "XLA Modules", "jit_step(1)", 0, 40_000),
    ev(DEV, "XLA Modules", "jit_step(1)", 40_000, 80_000),
    ev(DEV, "XLA Modules", "jit_step(1)", 80_000, 120_000),
] + [e for at in (0, 40_000, 80_000) for e in (
    ev(DEV, "XLA Ops", FWD % 3, at + 1000, at + 3000),
    ev(DEV, "XLA Ops", "%fusion.7 = bf16[8]{0} fusion()", at + 3000,
       at + 9000),
    ev(DEV, "XLA Ops", BWD % 4, at + 10_000, at + 14_000))]


def metric(name):
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"_lm_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


@pytest.fixture
def serve(monkeypatch):
    trace = spans.Trace(SERVE)
    monkeypatch.setattr(spans, "load", lambda record: trace)
    return {"device": {"kind": "TPU v5 lite", "count": 1}}


@pytest.fixture
def train(monkeypatch):
    trace = spans.Trace(TRAIN)
    monkeypatch.setattr(spans, "load", lambda record: trace)
    return {"device": {"kind": "TPU v5 lite", "count": 1}}


# ------------------------------------------------------- lib/spans.py
def test_the_innermost_span_of_the_driving_thread_wins():
    trace = spans.Trace(SERVE)
    assert (trace.lo, trace.hi) == (0, 1000)
    assert sum(e - s for s, e in trace.busy) == 300
    at = {s: name for s, _, name in trace.innermost()}
    assert at[90] == "serving.prefill.write_cache"
    assert at[258] == "serving.prefill"             # after its last child
    assert at[260] == "serving.engine_step"
    assert at[900] == spans.OUTSIDE
    assert "z" not in {sp.stats.get("request_id") for sp in trace.spans}


def test_the_pieces_of_a_cut_gap_add_up_to_the_gap():
    trace = spans.Trace(SERVE)
    table = spans.idle_by_span(trace)
    assert table == IDLE
    assert sum(table.values()) == 1000 - 300
    # and to what the frozen reduction calls idle, on the same events
    old = xplane.reduce([e[:5] for e in SERVE])
    assert sum(table.values()) / 1e9 == \
        pytest.approx(old["window_s"] - old["busy_s"])


def test_kernel_operations_are_found_by_the_programs_name():
    trace = spans.Trace(TRAIN)
    # the third execution is cut by the window's end, its kernels are not
    assert len(spans.kernel_ops(trace, "packed_flash_")) == 6
    assert len(spans.kernel_ops(trace, "packed_flash_fwd")) == 3
    assert len(spans.kernel_by_program({}, "packed_flash_")) == 0  # no trace
    assert len(spans.kernel_ops(trace, "packed_flash_bwd", 0, 40_000)) == 1
    assert spans.kernel_ops(trace, "flash_fwd") == []   # a name, not a part
    assert spans.kernel_ops(trace, "fusion") != []
    assert len(spans.kernel_ops(spans.Trace(SERVE),
                                "ragged_decode_attention")) == 2
    assert spans.shapes(BWD % 4)[0] == ("bf16", [2, 3, 128, 128], 2)
    assert spans.shapes(RAGGED % 5)[0] == ("f32", [2, 4, 8], 4)
    assert spans.shapes(RAGGED % 5)[4] == ("f32", [16, 4, 4, 8], 4)   # pool


def test_spans_are_joined_on_a_stat():
    trace = spans.Trace(SERVE)
    pairs = spans.joined(trace, "serving.add_request", "serving.prefill",
                         "request_id")
    # "b" was added in the window and not prefilled in it: no pair
    assert [(a.stats["request_id"], b.start - a.start)
            for a, b in pairs] == [("a", 50)]


def test_without_a_device_plane_or_without_spans_there_is_nothing():
    host_only = [e for e in SERVE if e[0] == HOST]
    assert not spans.Trace(host_only).ok
    assert spans.load({"trace_dir": None}) is None
    assert spans.load({}) is None
    no_spans = spans.Trace([e for e in SERVE
                            if not e[2].startswith("serving.")])
    assert no_spans.ok and no_spans.spans == []


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (BENCH / "layer_metrics").glob("*.py")
    if "spans" in p.read_text()))
def test_a_reader_gives_none_where_there_is_nothing_to_read(
        name, monkeypatch):
    """The CPU rehearsal (no device plane) and the parent of PR 27 (a
    device plane, no span and no kernel name): the metric is left out."""
    record = {"trace_dir": None, "device": {"kind": "cpu", "count": 1}}
    assert metric(name)(record, None) is None
    bare = spans.Trace([e for e in SERVE + TRAIN[1:]
                        if "serving." not in e[2] and "flash" not in e[2]])
    monkeypatch.setattr(spans, "load", lambda record: bare)
    assert metric(name)(record, None) is None


# ------------------------------------------------------ layer metrics
def test_the_four_idle_shares_add_up_to_the_idle_share(serve, capsys):
    got = {g: metric(f"serve_idle_{g}_pct")(serve, None) for g in (
        "in_prefill", "in_decode", "in_engine_other", "outside_engine")}
    assert got == pytest.approx({"in_prefill": 10.0, "in_decode": 32.0,
                                 "in_engine_other": 16.0,
                                 "outside_engine": 12.0})
    assert sum(got.values()) == pytest.approx(70.0)
    told = json.loads(capsys.readouterr().out.splitlines()[0])
    assert told["idle_seconds_by_innermost_span"]["outside"] == \
        pytest.approx(120e-9)


def test_prefill_is_split_into_host_and_device_time(serve):
    # one whole prefill: write_cache 150 ns of host time; the device was
    # busy [100, 200) of the prefill's [60, 260)
    assert metric("prefill_write_cache_ms")(serve, None) == \
        pytest.approx(150e-6)
    assert metric("prefill_device_ms")(serve, None) == pytest.approx(100e-6)


def test_ttft_wait_is_add_request_to_the_requests_own_prefill(serve, capsys):
    assert metric("ttft_wait_ms")(serve, None) == pytest.approx(50e-6)
    assert json.loads(capsys.readouterr().out)["ttft_wait_samples"] == 1


def test_ragged_kernel_time_and_share_of_hbm_bandwidth(serve, capsys):
    assert metric("ragged_kernel_ms")(serve, None) == pytest.approx(150e-6)
    # 10 positions x 4 heads x 8 x 4 B x (K and V) x 2 layers (2 calls in
    # a chunk of 1) = 5120 B in 150 ns, of 819 GB/s
    assert metric("ragged_kernel_hbm_pct")(serve, None) == \
        pytest.approx(100 * 5120 / 150e-9 / 819e9)
    told = json.loads(capsys.readouterr().out)["ragged_kernel"]
    assert told["bytes_required"] == 5120 and told["calls"] == 2


def test_flash_kernel_time_and_share_of_the_mxu_peak(train, capsys):
    # two whole executions, 2000 + 4000 ns of kernels each
    assert metric("train_flash_kernel_ms")(train, None) == \
        pytest.approx(6000e-6)
    # (2 + 4) x B 2 x (3 pairs x 128 lanes) x T 128^2 a step
    flops = 6 * 2 * 3 * 128 * 128 * 128
    assert metric("train_flash_mxu_pct")(train, None) == \
        pytest.approx(100 * flops / 6000e-9 / 197e12)
    told = json.loads(capsys.readouterr().out)["packed_flash_kernels"]
    assert told["executions"] == 2 and told["flops_required"] == 2 * flops


def test_every_new_metric_has_its_reader_and_its_entry():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in manifest["per_layer"]}
    readers = {p.stem for p in (BENCH / "layer_metrics").glob("*.py")
               if "spans" in p.read_text()}
    # PR 27's 11 and whatever later PRs add beside them
    assert len(readers) >= 11 and readers <= set(listed)
    for name in readers:
        assert listed[name]["source"] in ("device_trace", "program_span")
        # one cell when PR 27 wrote them; later cells append their names
        assert len(listed[name]["workloads"]) >= 1
    shares = [listed[n] for n in readers if n.startswith("serve_idle_")]
    assert {m["moves"] for m in shares} == {"serve_tokens_per_s"}


# ----------------------------------------------------- recorded slices
SLICES = sorted((BENCH / "fixtures" / "spans").glob("*.json"))


@pytest.mark.parametrize("path", SLICES or [None],
                         ids=[p.stem for p in SLICES] or None)
def test_readers_on_a_recorded_slice_of_a_chip_run(path, monkeypatch):
    if path is None:
        pytest.skip("no recorded slice yet")
    data = json.loads(path.read_text())
    events = [tuple(e) for e in data["events"]]
    trace = spans.Trace(events)
    monkeypatch.setattr(spans, "load", lambda record: trace)
    record = {"device": {"kind": "TPU v5 lite", "count": 1}}
    got = {name: metric(name)(record, None) for name in data["expect"]}
    assert got == pytest.approx(data["expect"], rel=1e-9)
    old = xplane.reduce([e[:5] for e in events])
    idle = [v for k, v in got.items() if k.startswith("serve_idle_")]
    if idle:        # the four shares are the frozen reader's idle share
        assert len(idle) == 4
        assert sum(idle) == pytest.approx(
            100.0 * (1.0 - old["busy_s"] / old["window_s"]))
    for name, value in got.items():
        if name.endswith("_pct"):
            assert 0.0 <= value < 100.0
