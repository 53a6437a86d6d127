"""A copy of the benchmark at tiny sizes, and a way to run one of its cells
on the CPU in a process of its own (four virtual devices), optionally
with a fault planted in the program first."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SIZES = {"paper_frame_4k": 64, "paper_batch_1k": 32}
# The four-chip job cell, not yet in BENCHMARK.json (no chip run proved
# it); its files are under bench/, and the tiny copy runs it.
PENDING = {
    "configs": [{"name": "paper_batch_1k", "source": "arXiv:2404.08245",
                 "file": "bench/configs/paper_batch_1k.json",
                 "reduced": ["frame_edge"], "why": "the distributed job"}],
    "workloads": [{"name": "batch1k.x4", "config": "paper_batch_1k",
                   "traffic": "jobs_lpt", "chips": 4,
                   "why": "90-frame jobs over four chips"}],
    "per_layer": [{"name": "chip_busy_spread_pct", "unit": "%",
                   "better": "lower", "source": "device_trace",
                   "layer": "pipeline scheduling", "moves": "mpix_per_s",
                   "workloads": ["batch1k.x4"]}],
}

DRIVE = """
import sys, time
t = time.perf_counter()
root, src, fault = sys.argv[1], sys.argv[2], sys.argv[3]
sys.path[:0] = [root, src]
from pathlib import Path
from bench import harness
if fault != "none":
    from bench.tests import faults
    faults.plant(fault, harness.cells(Path(root)))
sys.exit(harness.run(sys.argv[4:], t_start=t, root=Path(root),
                     require_tpu=False))
"""


def make_root(dst: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` under ``dst``, frames cut to a few
    dozen pixels and capacities to match."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in PENDING.items():
        names = {e["name"] for e in spec[key]}
        spec[key] += [e for e in entries if e["name"] not in names]
    for c in spec["configs"]:
        p = dst / c["file"]
        conf = json.loads(p.read_text())
        conf["frame_edge"] = SIZES[c["name"]]
        conf["ph"].update(max_features=1024, max_candidates=1024)
        if "frames_per_job" in conf:
            conf["frames_per_job"] = 10
        p.write_text(json.dumps(conf))
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


def drive(root: Path, workload: str, seed: int, *, seconds: float = 0.5,
          trace: int = 0, fault: str = "none"):
    """Run one cell of ``root`` on the CPU; returns (exit code, result line
    or None, standard error)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, "-c", DRIVE, str(root), str(ROOT / "src"), fault,
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, env=env, timeout=600)
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines else None
    return proc.returncode, result, proc.stderr
