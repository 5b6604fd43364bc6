"""What PR 37 added to the benchmark (CPU, not slow): six per-layer
metrics that read what the program now says of its own schedule, its
prefills and its expert loads. Each entry has a file, lists only cells
that exist and that report the end-to-end metric it moves, and gives None,
without an error, on the record of a run without a device plane (a
rehearsal), as the tests of PR 33 and PR 35 check theirs. The readers'
arithmetic is checked on a hand-written trace in
tests/benchmark_spans/test_spans_pr37.py."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
SERVING = ["gpt2m-serve-closed16", "pangu-ep16-serve-closed128",
           "gpt2m-serve-open-r80", "qwen3next-ep4-serve-closed128",
           "mellum2-pp4-serve-closed64", "gpt2m-serve-chunked"]
EXPERT = [SERVING[1], SERVING[3], SERVING[4]]
#: metric -> (unit, better, source, layer, cells)
NEW = {
    "prefill_share_pct": ("%", "lower", "program_span", "prefill program",
                          SERVING[:5]),
    "prefill_ms_per_ktok": ("ms", "lower", "program_span", "prefill program",
                            EXPERT),
    "prefill_expert_ms_per_ktok": ("ms", "lower", "device_trace",
                                   "model, serving layers", EXPERT),
    "prefill_expert_roofline_pct": ("%", "higher", "device_trace",
                                    "model, serving layers", EXPERT),
    "decode_row_occupancy_pct": ("%", "higher", "program_span",
                                 "serving engine", SERVING),
    "sched_waiting_rows": ("rows", "lower", "program_span", "serving engine",
                           SERVING),
}


def test_the_manifest_has_the_six_entries_behind_what_was_there():
    """Only what PR 37 owns: its six names, each as it wrote it, behind
    PR 35's last. A later PR may add entries, cells and configurations, and
    append its cells to an entry's `workloads`."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    listed = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, (unit, better, source, layer, cells) in NEW.items():
        m = dict(listed[name])
        assert set(cells) <= set(m.pop("workloads"))
        assert m == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "serve_tokens_per_s"}
        assert names.index("swa_attn_hbm_pct") < names.index(name)


@pytest.mark.parametrize("name", NEW)
def test_an_entry_has_its_file_and_lists_cells_that_report_what_it_moves(
        name):
    assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    cells = {w["name"] for w in MANIFEST["workloads"]}
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert set(NEW[name][4]) <= set(entry["workloads"]) <= cells
    assert set(entry["workloads"]) <= set(moved["workloads"])
    # a layer the manifest already named, letter for letter
    assert NEW[name][3] in {m["layer"] for m in MANIFEST["per_layer"]
                            if m["name"] not in NEW}
    if "roofline" in name:
        assert NEW[name][0] == "%"


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_a_rehearsals_record_and_gives_none(name):
    """A run without a device plane, a record of another runner, and one
    that took no trace: the metric is left out, nothing is raised."""
    from run import load_module                 # benchmarks/run.py
    compute = load_module("layer_metrics", name).compute
    for record in ({"facts": {}, "device": {"kind": "cpu", "count": 1}},
                   {"facts": {"work": {"config": {}}}, "trace_dir": None,
                    "device": {"kind": "cpu", "count": 1}},
                   {"trace_dir": str(ROOT / "benchmarks" / "configs"),
                    "device": {"kind": "TPU v5 lite", "count": 1}}):
        assert compute(record, None) is None
