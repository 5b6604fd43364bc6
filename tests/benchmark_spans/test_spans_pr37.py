"""The six readers PR 37 added (benchmarks/layer_metrics/prefill_share_pct,
prefill_ms_per_ktok, prefill_expert_ms_per_ktok,
prefill_expert_roofline_pct, decode_row_occupancy_pct, sched_waiting_rows
and benchmarks/lib/expert_ops.py) on a hand-written event list, where
every number can be checked by eye. The cut-down slice of a chip run of
cell 7 under benchmarks/fixtures/spans/ goes through test_spans.py's
`test_readers_on_a_recorded_slice_of_a_chip_run` with the others."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))

from lib import expert_ops, spans  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW = ("prefill_share_pct", "prefill_ms_per_ktok",
       "prefill_expert_ms_per_ktok", "prefill_expert_roofline_pct",
       "decode_row_occupancy_pct", "sched_waiting_rows")
#: 4 held experts of 8 x 6: gate and up are [4, 8, 6], down its transpose
UP, DOWN = "bf16[4,8,6]{2,1,0}", "bf16[4,6,8]{2,1,0}"
MOE = {"moe_shape": "4x8x6"}


def ev(plane, line, name, start, end, **stats):
    return (plane, line, name, start, end - start, stats)


def op(text, start, end):
    return ev(DEV, "XLA Ops", text, start, end)


#: a window of 100 us, two whole engine steps and one cut by its end. The
#: first admits one prompt of 2,000 tokens (two blocks of tokens through 2
#: expert layers: 4 calls) and is held by the budget with 5 waiting; its
#: chunk runs 3 rows of 16, one of them still eating its prompt and one
#: finishing after 4 trips (20 live row trips of 128). The second admits
#: nothing (every row taken, 3 waiting) and runs 3 live rows of 16
EVENTS = [
    ev(HOST, "main", "bench.window", 0, 100_000),
    ev(HOST, "main", "serving.engine_step", 1_000, 50_000, step=7),
    ev(HOST, "main", "serving.schedule", 1_100, 1_200, prefill=1,
       prefill_tokens=2000, chunked=1, decode=3, waiting=5, preempted=0,
       free_blocks=100, held_by="budget"),
    ev(HOST, "main", "serving.prefill", 2_000, 22_000, request_id="a",
       tokens=2000, moe_pairs=3000, moe_experts_hit=14,
       moe_full_buffer_layers=0, moe_batched_layers=1, moe_layer_calls=4,
       moe_fit_2x=1, moe_fit_4x=3, moe_max_load=900, **MOE),
    ev(HOST, "main", "serving.decode", 25_000, 45_000, num_seqs=3, rows=16,
       chunk=8, feeding_rows=1, live_row_trips=20, context_tokens=900,
       moe_layer_calls=16),
    ev(HOST, "main", "serving.engine_step", 50_000, 90_000, step=8),
    ev(HOST, "main", "serving.schedule", 50_100, 50_200, prefill=0,
       prefill_tokens=0, chunked=0, decode=3, waiting=3, preempted=0,
       free_blocks=90, held_by="rows"),
    ev(HOST, "main", "serving.decode", 51_000, 89_000, num_seqs=3, rows=16,
       chunk=8, feeding_rows=0, live_row_trips=24, context_tokens=1000,
       moe_layer_calls=16),
    ev(HOST, "main", "serving.engine_step", 95_000, 120_000, step=9),
    ev(HOST, "main", "serving.schedule", 95_100, 95_200, prefill=1,
       prefill_tokens=512, chunked=0, decode=3, waiting=0, preempted=0,
       free_blocks=80, held_by="none"),
    ev(HOST, "main", "serving.prefill", 96_000, 110_000, request_id="b",
       tokens=512, moe_pairs=800, moe_experts_hit=8, moe_layer_calls=2,
       moe_fit_2x=2, moe_fit_4x=2, moe_max_load=210, **MOE),
    # the first prefill's expert products, found by the weights' shape:
    # the grouped kernel on gate / up, and on down by the transpose
    op(f"%ragged-dot-none.3 = f32[2048,6]{{1,0}} custom-call(bf16[2048,8]"
       f"{{1,0}} %x, {UP} %w, s32[4]{{0}} %sizes)", 2_500, 6_500),
    op(f"%ragged-dot-none.5 = f32[2048,8]{{1,0}} custom-call(bf16[2048,6]"
       f"{{1,0}} %a, {DOWN} %w, s32[4]{{0}} %sizes)", 7_000, 9_000),
    # left out: another fusion of the prefill, and the containers that
    # hold the products (the map over blocks, the switch of forms)
    op("%fusion.9 = bf16[2048,8]{1,0} fusion(bf16[2048,8]{1,0} %p)", 10_000,
       12_000),
    op(f"%while.2 = (s32[], {UP}) while((s32[], {UP}) %t), condition=%c, "
       "body=%b", 2_400, 15_000),
    op(f"%conditional.4 = f32[1024,8]{{1,0}} conditional(s32[] %i, {UP} %w)",
       2_450, 6_600),
    # a chunk's batched products name the weights too: the decode's
    op(f"%fusion.12 = f32[4,16,6]{{2,1,0}} fusion(bf16[4,16,8]{{2,1,0}} %r, "
       f"{UP} %w)", 30_000, 31_000),
    # the cut prefill's, inside the window
    op(f"%ragged-dot-none.3 = f32[512,6]{{1,0}} custom-call(bf16[512,8]"
       f"{{1,0}} %x, {UP} %w, s32[4]{{0}} %sizes)", 97_000, 99_000),
]


def metric(name):
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"_lm37_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


def record_of(events):
    return {"device": {"kind": "TPU v5 lite", "count": 1},
            "_spans": spans.Trace(events)}


def told(capsys, key):
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return next(l[key] for l in lines if key in l)


def without(events, *stats):
    return [e[:5] + ({k: v for k, v in e[5].items() if k not in stats},)
            for e in events]


# -------------------------------------------------- the prefill layer
def test_prefill_takes_its_share_of_the_whole_steps(capsys):
    """One whole prefill of 20 us in two whole steps of 49 + 40 us; the
    step and the prefill the window's end cuts are left out."""
    assert metric("prefill_share_pct")(record_of(EVENTS), None) == \
        pytest.approx(100 * 20_000 / 89_000)
    assert told(capsys, "prefill_share") == {
        "steps": 2, "prefills": 1, "step_seconds": pytest.approx(89e-6),
        "prefill_seconds": pytest.approx(20e-6)}
    # a window that ends inside the third step but behind its prefill: the
    # prefill lies wholly in the window, its step does not, and it is left
    # out with its step (both sums are over the same steps)
    longer = [ev(HOST, "main", "bench.window", 0, 115_000)] + EVENTS[1:]
    assert metric("prefill_share_pct")(record_of(longer), None) == \
        pytest.approx(100 * 20_000 / 89_000)
    assert metric("prefill_ms_per_ktok")(record_of(longer), None) == \
        pytest.approx(1e3 * 0.034 / 2512)


def test_prefill_time_a_thousand_prompt_tokens():
    # 0.020 ms for 2,000 tokens
    assert metric("prefill_ms_per_ktok")(record_of(EVENTS), None) == \
        pytest.approx(0.010)


def test_the_expert_products_are_found_by_the_weights_shape(capsys):
    """Inside the whole prefill, the operations that name [4, 8, 6] or its
    transpose and hold no others: 4 + 2 us. The fusion without the shape,
    the `while`, the `conditional` and the chunk's products are not among
    them; the cut prefill's product is in `window_seconds` alone."""
    record = record_of(EVENTS)
    seen = expert_ops.traced(record)
    assert seen["shape"] == [4, 8, 6] and seen["itemsize"] == 2
    assert seen["layers"] == 2          # 16 calls in a chunk of 8 trips
    assert [(p["tokens"], p["ops"]) for p in seen["prefills"]] == [(2000, 2)]
    assert seen["seconds"] == pytest.approx(6e-6)
    assert seen["window_seconds"] == pytest.approx(8e-6)
    assert metric("prefill_expert_ms_per_ktok")(record, None) == \
        pytest.approx(0.003)            # 0.006 ms for 2,000 tokens
    (p,) = told(capsys, "prefill_expert")["prefills"]
    # 3,000 pairs in 4 calls on 4 held experts: 187.5 a held expert a call
    assert p == {"tokens": 2000, "calls": 4,
                 "max_load_over_routed_mean": pytest.approx(900 / 187.5),
                 "fit_2x": 0.25, "fit_4x": 0.75, "batched": 0.25,
                 "experts_hit_a_call": 3.5,
                 "device_ms": pytest.approx(0.006)}


def test_the_expert_products_share_of_their_roofline(capsys):
    """3,000 pairs x 6 x 8 x 6 operations at 197 TFLOP/s against the
    weights of 3.5 experts a call, once in each of 2 layers, 3 x 8 x 6 x
    2 B each, at 819 GB/s: the MXU bounds; over 6 us of device time."""
    flops, size = 6 * 8 * 6 * 3000, 3 * 8 * 6 * 2 * 2 * 3.5
    assert flops / 197e12 > size / 819e9
    assert metric("prefill_expert_roofline_pct")(record_of(EVENTS), None) \
        == pytest.approx(100 * flops / 197e12 / 6e-6)
    need = told(capsys, "prefill_expert_required")
    assert need["bound"] == "bf16_flops_per_s"
    assert (need["flops"], need["bytes"]) == (flops, size)
    # where few pairs reach many experts the weights bound it
    few = [e[:5] + ({**e[5], "moe_pairs": 30},) if e[2] == "serving.prefill"
           else e for e in EVENTS]
    assert metric("prefill_expert_roofline_pct")(record_of(few), None) == \
        pytest.approx(100 * size / 819e9 / 6e-6)
    assert told(capsys, "prefill_expert_required")["bound"] == \
        "hbm_bytes_per_s"


def test_without_a_chunk_to_count_the_layers_there_is_no_roofline():
    record = record_of([e for e in EVENTS if e[2] != "serving.decode"])
    assert expert_ops.traced(record)["layers"] is None
    assert metric("prefill_expert_roofline_pct")(record, None) is None
    assert metric("prefill_expert_ms_per_ktok")(record, None) == \
        pytest.approx(0.003)


# ------------------------------------------- the engine and its schedule
def test_a_chunk_of_three_live_rows_of_sixteen(capsys):
    """20 + 24 live row trips of 2 chunks x 16 rows x 8 trips."""
    assert metric("decode_row_occupancy_pct")(record_of(EVENTS), None) == \
        pytest.approx(100 * 44 / 256)
    assert told(capsys, "decode_rows") == {
        "chunks": 2, "rows": 16.0, "num_seqs": 3.0, "feeding_rows": 0.5}
    second = [e for e in EVENTS if not (e[2] == "serving.decode"
                                        and e[3] < 50_000)]
    assert metric("decode_row_occupancy_pct")(record_of(second), None) == \
        pytest.approx(100 * 3 / 16)


def test_a_step_held_by_the_budget_and_what_waits_behind_it(capsys):
    """Three schedules lie wholly in the window (the third step's too,
    though the window's end cuts the step): 5, 3 and 0 requests left
    waiting, a step each held by the budget, by the rows and by nothing;
    2,000 + 512 prompt tokens admitted in three."""
    assert metric("sched_waiting_rows")(record_of(EVENTS), None) == \
        pytest.approx(8 / 3)
    assert told(capsys, "schedule") == {
        "steps": 3, "held_by": {"budget": pytest.approx(1 / 3),
                                "none": pytest.approx(1 / 3),
                                "rows": pytest.approx(1 / 3)},
        "prefill": pytest.approx(2 / 3),
        "prefill_tokens": pytest.approx(2512 / 3),
        "chunked": pytest.approx(1 / 3), "decode": 3.0, "preempted": 0.0,
        "free_blocks": 90.0, "free_blocks_least": 80}


# ------------------------------------------------- nothing to read
@pytest.mark.parametrize("name", NEW)
def test_a_trace_without_the_new_stats_gives_none(name):
    """The parent's trace: the spans are there (PR 27), `rows`, `waiting`,
    `moe_shape` and `moe_layer_calls` are not. The two metrics that read
    what the parent already wrote (`tokens`, the spans' times) still
    read; the other four are left out. No device plane: all are."""
    old = without(EVENTS, "rows", "feeding_rows", "waiting", "held_by",
                  "prefill_tokens", "chunked", "moe_shape",
                  "moe_layer_calls", "moe_fit_2x", "moe_fit_4x")
    got = metric(name)(record_of(old), None)
    if name in ("prefill_share_pct", "prefill_ms_per_ktok"):
        assert got is not None
    else:
        assert got is None
    host_only = record_of([e for e in EVENTS if e[0] == HOST])
    assert metric(name)(host_only, None) is None
    assert metric(name)({"trace_dir": None, "device": {"kind": "cpu"}},
                        None) is None
    no_prefill = [e for e in EVENTS if not e[2].startswith("serving.prefill")]
    if name.startswith("prefill_"):     # every prompt rides the scan
        assert metric(name)(record_of(no_prefill), None) is None


def test_no_new_reader_names_a_family_or_a_configurations_key():
    """One reader and one metric name serve the three expert cells."""
    words = ("pangu", "qwen", "mellum", "gpt2", "hybrid", "swa", "latent",
             "n_routed_experts", "num_experts", "moe_intermediate_size",
             "hidden_size", "facts")
    for path in [BENCH / "lib" / "expert_ops.py"] + [
            BENCH / "layer_metrics" / f"{n}.py" for n in NEW]:
        text = path.read_text().lower()
        assert not [w for w in words if w in text], path.name
