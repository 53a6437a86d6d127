"""The harness: cells found by name, no result without a chip, and a whole
run of each cell at a tiny size on the CPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny

ROOT = tiny.ROOT


@pytest.mark.parametrize("tiny_copy", [False, True])
def test_cells_resolve_their_files(tmp_path, tiny_copy):
    root = tiny.make_root(tmp_path) if tiny_copy else ROOT
    table = harness.cells(root)
    assert set(table) == ({"frame4k.whole", "batch1k.x4"} if tiny_copy
                          else {"frame4k.whole"})
    for cell in table.values():
        assert cell["entry"].exists()
        for m in cell["end_to_end"] + cell["per_layer"]:
            assert callable(harness.metric_reader(root, m["name"]).read)
    if not tiny_copy:
        return
    names = {m["name"] for m in table["batch1k.x4"]["per_layer"]}
    assert "chip_busy_spread_pct" in names
    assert "chip_busy_spread_pct" not in {
        m["name"] for m in table["frame4k.whole"]["per_layer"]}


def test_added_cell_and_metric_are_files_alone(tmp_path):
    root = tiny.make_root(tmp_path)
    (root / "bench" / "configs" / "dummy.json").write_text(json.dumps(
        {**json.loads((root / "bench/configs/paper_frame_4k.json")
                      .read_text()), "frame_edge": 32}))
    (root / "bench" / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"entry": "run", "render_threads": 1,
         "frames": 2, "check_frames": 1}))
    (root / "bench" / "metrics" / "dummy_count.py").write_text(
        "def read(run):\n    return len(run.units)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy", "source": "a test",
                            "file": "bench/configs/dummy.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "dummy.cell", "config": "dummy",
                              "traffic": "dummy_mix", "chips": 1,
                              "why": "a test"})
    spec["per_layer"].append({"name": "dummy_count", "unit": "count",
                              "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "mpix_per_s",
                              "workloads": ["dummy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    table = harness.cells(root)
    assert "dummy.cell" in table
    assert table["dummy.cell"]["config"]["frame_edge"] == 32
    assert "dummy_count" in {m["name"]
                             for m in table["dummy.cell"]["per_layer"]}
    code, result, err = tiny.drive(root, "dummy.cell", 5, trace=1)
    assert code == 0, err
    assert result["correct"] is True
    assert result["metrics"]["dummy_count"]["value"] >= 1


def _run_py(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "frame4k.whole",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("workload,trace", [("frame4k.whole", 0),
                                            ("frame4k.whole", 1),
                                            ("batch1k.x4", 0),
                                            ("batch1k.x4", 1)])
def test_tiny_run_is_correct(tmp_path, workload, trace):
    root = tiny.make_root(tmp_path)
    code, result, err = tiny.drive(root, workload, 2 ** 31 + 7, trace=trace)
    assert code == 0, err
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    want = {m["name"] for m in harness.cells(root)[workload][
        "per_layer" if trace else "end_to_end"]}
    got = set(result["metrics"])
    # Device readings (memory, trace shares) are absent on the CPU.
    assert got <= want
    assert ("window_compiles" in got) if trace else ("mpix_per_s" in got)
    if trace:
        assert result["metrics"]["window_compiles"]["value"] == 0
        assert "breakdown" in result
