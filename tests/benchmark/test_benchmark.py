"""The benchmark's own tests (CPU, not slow): the manifest keeps to its
contract, every file it names is there, the yardstick's arithmetic is
right on hand-made inputs and on the recorded traces, the plain reference
agrees with the program, and the harness takes a new cell and a new layer
metric as files. No TPU topology is described here."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))

from lib import chip, traffic, xplane  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
FIXTURES = sorted(p.name for p in (BENCH / "fixtures").glob("*.json"))


def cells_of(metric):
    return set(metric.get("workloads", CELLS))


def run_bench(*args, manifest=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # one CPU device, as on one chip
    cmd = [sys.executable, str(BENCH / "run.py"), *args]
    if manifest:
        cmd += ["--manifest", str(manifest)]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


# ------------------------------------------------------------- manifest
def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["command"][-1].startswith(MANIFEST["paths"][0] + "/")
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_and_units_keep_to_the_allowed_characters(section):
    entries = MANIFEST[section]
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("config", "traffic"):
            assert key not in e or NAME.match(e[key]), e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_exists(cell):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    data = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    assert data["config"] == entry["config"]
    assert data["traffic"] == entry["traffic"]
    assert data["chips"] == entry["chips"] and entry["chips"] in (1, 4)
    assert (BENCH / "runners" / f"{data['runner']}.py").is_file()
    assert (BENCH / "traffic" / f"{data['traffic']}.json").is_file()
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == data["config"])
    assert (ROOT / config["file"]).is_file()
    assert (ROOT / json.loads((ROOT / config["file"]).read_text())
            ["reference"]).is_file()
    readers = {p.stem for p in (BENCH / "layer_metrics").glob("*.py")}
    for m in MANIFEST["per_layer"]:
        if cell in cells_of(m):     # its own reader, or its stem's
            assert {m["name"], m["name"].rsplit(".", 1)[0]} & readers


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in MANIFEST["end_to_end"]
               if cell in cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in cells_of(m) for m in MANIFEST["per_layer"])
    for m in MANIFEST["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_moves_names_an_end_to_end_metric_of_the_same_cells(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    target = next(e for e in MANIFEST["end_to_end"]
                  if e["name"] == m["moves"])
    assert cells_of(m) <= cells_of(target)
    assert "bound" not in m


# ------------------------------------------------------------ yardstick
def test_flops_per_token_counts_causal_attention_once():
    # gpt2-small: 12 * 12 * 768^2 + 50257 * 768 matmul parameters
    assert chip.gpt_matmul_params(768, 12, 50257) == 84934656 + 38597376
    assert chip.gpt_train_flops_per_token(768, 12, 50257, 1024) == \
        6 * 123532032 + 6 * 12 * 768 * 1024
    with pytest.raises(KeyError):
        chip.peaks("a chip nobody measured")


def test_percentile_states_its_sample_count():
    assert chip.percentile([], 95) == (None, 0)
    assert chip.percentile(list(range(101)), 95) == (95.0, 101)


def test_latency_stats_are_mean_and_percentiles_in_ms():
    from runners import serve_closed
    assert serve_closed.latency_stats("ttft", []) == {}
    out = serve_closed.latency_stats("ttft", [0.1 * i for i in range(101)])
    assert set(out) == {"ttft_mean_ms", "ttft_p50_ms", "ttft_p90_ms",
                        "ttft_p95_ms"}
    assert out["ttft_mean_ms"] == pytest.approx(5000.0)
    assert out["ttft_p95_ms"] == pytest.approx(9500.0)


#: two device lines' worth of events on one plane: a `while` that encloses
#: two fusions, an overlapping copy, one gap of 30 ns under a host span
HAND = [
    ("/device:TPU:0", "XLA Ops", "while.1", 100, 60),
    ("/device:TPU:0", "XLA Ops", "fusion.a", 100, 20),
    ("/device:TPU:0", "XLA Ops", "fusion.b", 130, 30),
    ("/device:TPU:0", "XLA Ops", "copy.7", 150, 20),       # overlaps
    ("/device:TPU:0", "XLA Ops", "fusion.a", 200, 40),
    ("/device:TPU:0", "XLA Modules", "jit_step", 100, 140),  # other line
    ("/host:CPU", "python3", "bench.window", 90, 160),
    ("/host:CPU", "python3", "bench.fetch_loss", 165, 40),
    ("/host:CPU", "python3", "bench.dispatch", 92, 5),
]


def test_an_operation_is_named_without_suffix_and_layout():
    hlo = ("%copy.2 = f32[512,32,16,64]{0,3,2,1:T(8,128)} copy(f32[512,32,16,"
           "64]{3,2,1,0:T(8,128)} %fusion)")
    assert xplane.short_name(hlo) == "copy copy f32[512,32,16,64]"
    kernel = ("%ragged_decode_attention.271 = f32[16,16,64]{2,1,0:T(8,128)S(1)}"
              " custom-call(s32[16,32]{1,0} %x), custom_call_target=\"tpu_c\"")
    assert xplane.short_name(kernel) == \
        "ragged_decode_attention custom-call f32[16,16,64]"
    assert xplane.short_name("fusion.a") == "fusion.a"


def test_xplane_reduction_on_a_hand_written_event_list():
    out = xplane.reduce(HAND, chips=1)
    # busy: [100, 170) and [200, 240) = 110 ns of the window [90, 250)
    assert out["busy_s"] == pytest.approx(110e-9)
    assert out["window_s"] == pytest.approx(160e-9)
    ops = dict(map(tuple, out["breakdown"]["device_ops"]))
    # own time: fusion.a 20 + 40, fusion.b 30, copy 20, while 60 - 50
    assert out["breakdown"]["device_ops"][0][0] == "fusion.a"
    assert ops["fusion.a"] == pytest.approx(60e-9)
    assert ops["while.1"] == pytest.approx(10e-9)
    assert out["modules"] == [["jit_step", 1, pytest.approx(140e-9)]]
    name, seconds = out["breakdown"]["idle_gaps"][0]
    assert (name, seconds) == ("bench.fetch_loss", pytest.approx(30e-9))
    assert xplane.reduce([e for e in HAND if "device" not in e[0]]) is None


@pytest.mark.parametrize("fixture", FIXTURES or [None])
def test_reduction_reads_the_recorded_trace(fixture):
    if fixture is None:
        pytest.skip("no recorded trace yet")
    data = json.loads((BENCH / "fixtures" / fixture).read_text())
    out = xplane.reduce(map(tuple, data["events"]), chips=1)
    assert out["busy_s"] == pytest.approx(data["expect"]["busy_s"])
    assert out["window_s"] == pytest.approx(data["expect"]["window_s"])
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["breakdown"]["device_ops"][0][0] == data["expect"]["top_op"]
    assert 1 <= len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10


# -------------------------------------------------------------- traffic
def test_traffic_sizes_are_the_files_and_order_and_tokens_the_seeds():
    mix = json.loads((BENCH / "traffic" / "closed16-mixed.json").read_text())

    def first(seed, n=60):
        it = traffic.closed_loop_sizes(mix, seed)
        return [next(it) for _ in range(n)]

    # a pure function of the seed; another seed sends the same sizes,
    # block for block, in another order
    assert first(2**31 + 17) == first(2**31 + 17) != first(2**31 + 18)
    for at in (0, 20, 40):
        a, b = first(1)[at:at + 20], first(2)[at:at + 20]
        assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
        assert sorted(m for _, m in a) == sorted(m for _, m in b)
    lens = [p for p, _ in first(1, 20)]
    assert [lens.count(n) for n in mix["prompt_lens"]] == [5, 6, 5, 3, 1]
    outs = sorted(m for _, m in first(1, 20))
    assert outs[0] == 32 and outs[-1] == 192 and len(set(outs)) == 20
    big = 2**31 + 17
    a = traffic.prompt(big, 4, 64, 50257)
    assert np.array_equal(a, traffic.prompt(big, 4, 64, 50257))
    assert not np.array_equal(a, traffic.prompt(big, 5, 64, 50257))
    assert not np.array_equal(a, traffic.prompt(big + 1, 4, 64, 50257))
    lm = json.loads((BENCH / "traffic" / "train-b24-t1024.json").read_text())
    x, y = traffic.lm_batches(lm, big, 50257)[3]
    assert x.shape == (24, 1024) and x.dtype == np.int32
    assert np.array_equal(x, traffic.lm_batches(lm, big, 50257)[3][0])
    assert not np.array_equal(x, traffic.lm_batches(lm, big + 1, 50257)[3][0])


# ------------------------------------------------------------ reference
def test_reference_agrees_with_the_program_at_the_rehearsal_size():
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from lib import gpt2, reference_gpt2

    config = json.loads((BENCH / "configs" / "gpt2-small.json").read_text())
    size = gpt2.sizes(config, rehearse=True)
    model = gpt2.build_model(size, seed=2**31 + 5)
    model.eval()
    ids = traffic.prompt(7, 0, 2 * 96, size["vocab_size"]).reshape(2, 96)
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    params = {k: p._value for k, p in model.named_parameters()}
    want = np.asarray(reference_gpt2.logits(
        params, jnp.asarray(ids), size["n_layer"], size["n_head"]))
    # float32 on both sides with exact products (conftest pins "highest"):
    # what is left is summation order
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert abs(got).max() > 0.05        # the comparison is not of zeros
    # the seeded weights are GPT-2's published initialisation
    assert float(np.std(np.asarray(params["wte.weight"]))) == \
        pytest.approx(0.02, rel=0.05)
    assert np.all(np.asarray(params["blocks.0.ln1.weight"]) == 1)


# -------------------------------------------------------------- harness
def test_without_a_tpu_the_benchmark_refuses_and_prints_no_result():
    done = run_bench("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0")
    assert done.returncode != 0
    assert "no TPU found" in done.stderr
    assert '"correct"' not in done.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell_ends_in_a_line_marked_as_one(cell, tmp_path):
    done = run_bench("--workload", cell, "--seed", str(2**31 + 11),
                     "--seconds", "2", "--trace", "1", "--rehearse",
                     "--out", str(tmp_path))
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["checks_passed"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"]
    # a CPU number never stands under a device metric's name
    assert all(m["value"] is None for m in last["metrics"].values())
    assert '"correct": true' not in done.stdout


def test_a_new_cell_and_a_new_layer_metric_are_files_and_an_entry(tmp_path):
    """What a later PR does: add a workload file, a layer-metric file and
    their entries; no file that is there is edited."""
    tag = f"_tmp_{os.getpid()}"
    cell_file = BENCH / "workloads" / f"{tag}.json"
    metric_file = BENCH / "layer_metrics" / f"{tag}.steps.py"
    cell = json.loads((BENCH / "workloads" / f"{CELLS[0]}.json").read_text())
    manifest = json.loads(json.dumps(MANIFEST))
    base = next(w for w in manifest["workloads"] if w["name"] == CELLS[0])
    manifest["workloads"].append(dict(base, name=tag))
    for m in manifest["end_to_end"]:
        if "workloads" in m and CELLS[0] in m["workloads"]:
            m["workloads"].append(tag)
    manifest["per_layer"].append({
        "name": f"{tag}.steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s", "workloads": [tag]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    try:
        cell_file.write_text(json.dumps(cell))
        metric_file.write_text(
            "def compute(record, trace):\n"
            "    return record['facts']['steps']\n")
        done = run_bench("--workload", tag, "--seed", "3", "--seconds", "1",
                         "--trace", "1", "--rehearse", "--out", str(tmp_path),
                         manifest=tmp_path / "BENCHMARK.json")
    finally:
        cell_file.unlink(missing_ok=True)
        metric_file.unlink(missing_ok=True)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(last["metrics"]) == [f"{tag}.steps"]
