"""A PR's benchmark test asserts what that PR added, and the manifest only
grows. So a later PR adds a configuration, a traffic mix, a cell and a
layer metric as new files and appended entries, and edits no file that is
there. This test makes that addition on a copy of the benchmark and runs
the benchmark's own tests against the copy (CPU, not slow)."""
import json
import os
import re
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
#: the cell and configuration the probe copies, and the probe's names
CELL, CONFIG = "gpt2m-serve-closed16", "gpt2-medium"
PROBE_CELL, PROBE_CONFIG = "probe-serve-closed16", "probe-gpt2-medium"
PROBE_TRAFFIC, PROBE_METRIC = "probe-closed16-mixed", "probe_decode_chunk_ms"
#: tests that drive `benchmarks/run.py` or `precision_witness.py` in a
#: child process; a new cell's rehearsal is covered by test_benchmark.py's
#: `test_a_new_cell_and_a_new_layer_metric_are_files_and_an_entry`
DRIVE_THE_HARNESS = ("rehearsal", "witness", "lower_precision",
                     "without_a_tpu", "files_and_an_entry")


def add_the_probe(copy):
    """What a later PR adds: four new files and appended entries."""
    bench = copy / "benchmarks"
    config = json.loads((bench / "configs" / f"{CONFIG}.json").read_text())
    cell = json.loads((bench / "workloads" / f"{CELL}.json").read_text())
    assert cell["runner"] == "serve_closed"
    (bench / "configs" / f"{PROBE_CONFIG}.json").write_text(
        json.dumps(config))
    shutil.copy(bench / "traffic" / f"{cell['traffic']}.json",
                bench / "traffic" / f"{PROBE_TRAFFIC}.json")
    (bench / "workloads" / f"{PROBE_CELL}.json").write_text(json.dumps(
        dict(cell, config=PROBE_CONFIG, traffic=PROBE_TRAFFIC)))
    shutil.copy(bench / "layer_metrics" / "decode_chunk_ms.py",
                bench / "layer_metrics" / f"{PROBE_METRIC}.py")

    manifest = json.loads((copy / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    manifest["configs"].append(dict(
        entry, name=PROBE_CONFIG,
        file=f"benchmarks/configs/{PROBE_CONFIG}.json"))
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    manifest["workloads"].append(dict(
        entry, name=PROBE_CELL, config=PROBE_CONFIG, traffic=PROBE_TRAFFIC))
    for m in manifest["end_to_end"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(PROBE_CELL)
    for m in manifest["per_layer"]:
        if m["name"] in ("decode_chunk_ms", "engine_schedule_ms"):
            m["workloads"].append(PROBE_CELL)
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "decode_chunk_ms")
    manifest["per_layer"].append(dict(entry, name=PROBE_METRIC,
                                      workloads=[PROBE_CELL]))
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))


def outcomes(junit):
    """{(module, test): passed} from a junit file."""
    got = {}
    for case in ET.parse(junit).iter("testcase"):
        module = case.get("classname").rsplit(".", 1)[-1]
        got[module, case.get("name")] = not [
            x for x in case if x.tag in ("failure", "error", "skipped")]
    return got


def test_a_new_configuration_and_cell_are_new_files_and_appended_entries(
        tmp_path):
    skip = shutil.ignore_patterns("__pycache__", "_tmp_*")
    for sub in ("benchmarks", "tests/benchmark", "tests/benchmark_spans"):
        shutil.copytree(ROOT / sub, tmp_path / sub, ignore=skip)
    # conftest.py: the CPU platform and matmul precision the tests assume
    for name in ("BENCHMARK.json", "pytest.ini", "tests/conftest.py"):
        shutil.copy(ROOT / name, tmp_path / name)
    (tmp_path / "paddle_tpu").symlink_to(ROOT / "paddle_tpu")
    add_the_probe(tmp_path)

    junit = tmp_path / "probe.xml"
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark",
         "tests/benchmark_spans", "-q", "-m", "not slow",
         "-p", "no:cacheprovider", "-p", "no:randomly",
         "--ignore", f"tests/benchmark/{Path(__file__).name}",
         "-k", " and ".join(f"not {w}" for w in DRIVE_THE_HARNESS),
         f"--junitxml={junit}"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stdout[-4000:]

    got = outcomes(junit)
    # every PR's own test that the manifest only grew, and the probe's
    # cases of the tests parametrised by cell and by metric
    owned = {(p.stem, name)
             for p in (tmp_path / "tests" / "benchmark").glob("test_*.py")
             for name in re.findall(r"^def (test_\w*manifest_only_grew)\(",
                                    p.read_text(), re.M)}
    assert len(owned) >= 3
    probes = {
        ("test_benchmark", f"test_every_file_a_cell_names_exists"
                           f"[{PROBE_CELL}]"),
        ("test_benchmark", f"test_moves_names_an_end_to_end_metric_of_the_"
                           f"same_cells[{PROBE_METRIC}]")}
    for key in owned | probes:
        assert got.get(key) is True, key
