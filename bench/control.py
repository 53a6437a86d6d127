"""The control: the plain reference in the program's place, one precision
down, which the correctness check has to reject.

The configurations state float32 frames; the control rounds every frame
to bfloat16 and computes the reference on that, the step a later change
to a narrower frame type would take.  ``install(cell)`` replaces the
program entry a cell's driver calls; the benchmark's own runs never do.

    python3 bench/control.py --workload <cell> --seed <n> [--seed ...] \
        --seconds <s>

runs the cell once per seed with the control installed and prints each
run's compared numbers (its result line), for the limits in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def lower(img: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 -> float32: round to nearest even, 8 bits kept."""
    bits = np.ascontiguousarray(img, np.float32).view(np.uint32)
    bias = ((bits >> 16) & 1) + np.uint32(0x7FFF)
    return ((bits + bias) & np.uint32(0xFFFF0000)).view(np.float32)


def _diagram(rows: np.ndarray, capacity: int):
    """Reference rows as the program's padded host diagram."""
    from repro.core.pixhomology import Diagram
    c = len(rows)
    f = max(capacity, c)
    birth = np.full(f, -np.inf, np.float32)
    death = np.full(f, -np.inf, np.float32)
    pb = np.full(f, -1, np.int32)
    pd = np.full(f, -1, np.int32)
    birth[:c], death[:c] = rows[:, 0], rows[:, 1]
    pb[:c], pd[:c] = rows[:, 2], rows[:, 3]
    return Diagram(birth, death, pb, pd, np.int32(c), np.int32(0),
                   np.bool_(False))


def install(config: dict) -> None:
    """Put the lowered reference in place of ``PHEngine.run`` and
    ``PHEngine.run_distributed`` for a configuration's frames."""
    from repro.ph import engine as engine_mod
    from repro.pipeline.driver import PipelineResult, _summarize
    from bench import frames, reference
    from repro.ph.engine import PHResult, RegrowStats
    factor = float(config["filter_factor"])
    recipe = config["recipe"]

    def run(self, image, truncate_value=None):
        img = lower(np.asarray(image))
        t = frames.threshold(img, factor)
        rows = reference.diagram(img, t)
        cfg = self.config
        return PHResult(_diagram(rows, cfg.max_features), cfg,
                        RegrowStats(0, cfg.max_features, cfg.max_candidates,
                                    False), t)

    def run_distributed(self, images, *, ctx=None, strategy=None, **_):
        t0 = time.time()
        out = {}
        for image_id, size in images:
            img = frames.render(image_id, size, recipe)
            out[image_id] = _summarize(run(self, img).diagram)
        return PipelineResult(out, 1, 0, time.time() - t0)

    engine_mod.PHEngine.run = run
    engine_mod.PHEngine.run_distributed = run_distributed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.cells(ROOT)[args.workload]
    install(cell["config"])
    for seed in args.seed:
        t = time.perf_counter()
        harness.run(["--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(args.seconds), "--trace", "0"],
                    t_start=t, root=ROOT, require_tpu=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
