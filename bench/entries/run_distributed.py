"""Driver of ``PHEngine.run_distributed``: the paper's distributed job.

Jobs of ``config["frames_per_job"]`` frame ids run back to back over every
chip of the cell (``auto_context()``), with the config's Variant-3
strategy.  The program's executors render each frame from its id on the
host (Variant 1, ``load_self``) and take its Variant-2 threshold, so the
benchmark hands over ids only.  Ids are drawn from the seed without
repeats, so no frame is computed twice in a run.  Set-up warms the
round's sharded program with a job of one round.
"""
from __future__ import annotations

import time

import numpy as np

from bench import frames as gen
from bench import reference
from bench.harness import Cell, Check, Unit, engine_config

ID_SPACE = 2 ** 40


class Driver(Cell):

    def setup(self):
        from repro.launch.mesh import auto_context
        from repro.ph import PHEngine
        cfg = self.config
        self.size = int(cfg["frame_edge"])
        self.per_job = int(cfg["frames_per_job"])
        self.engine = PHEngine(engine_config(cfg))
        self.ctx = auto_context()
        if self.ctx.mesh.size != len(self.devices):
            raise RuntimeError(f"mesh of {self.ctx.mesh.size} devices for a "
                               f"cell of {len(self.devices)} chips")
        self.rng = np.random.default_rng(self.seed)
        self.used: set[int] = set()
        t = time.perf_counter()
        self._job(self._ids(self.ctx.mesh.size))
        self.setup_parts["warm_job_s"] = time.perf_counter() - t
        self.jobs: list = []

    def _ids(self, n: int) -> list[int]:
        out = []
        while len(out) < n:
            for i in self.rng.choice(ID_SPACE, n - len(out), replace=False):
                if int(i) not in self.used:
                    self.used.add(int(i))
                    out.append(int(i))
        return out

    def _job(self, ids):
        return self.engine.run_distributed(
            [(i, self.size) for i in ids], ctx=self.ctx,
            strategy=self.config["strategy"])

    def window(self, seconds):
        units = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ids = self._ids(self.per_job)
            self.attempted += len(ids)
            with self.span("job"):
                try:
                    res = self._job(ids)
                except RuntimeError:
                    res = None
            got = {} if res is None else res.diagrams
            self.failed += sum(1 for i in ids if i not in got)
            units.append(Unit(len(got) * self.size * self.size / 1e6,
                              time.perf_counter()))
            self.jobs.append((ids, got))
        return units

    def after_trace(self):
        # Candidates of a sample of the first job's frames against the
        # tier the job's rounds ran at.
        if not self.jobs:
            return
        ids, _ = self.jobs[0]
        k = int(self.traffic["candidate_sample"])
        cap = self.engine.config.max_candidates
        for entry in self.engine.regrow_log:
            cap = max(cap, entry["to"][1])
        cand = 0
        for i in ids[:k]:
            img = gen.render(i, self.size, self.config["recipe"])
            cand += self.engine.num_candidates(img)
        self.counters.update(candidates=cand, candidate_capacity=cap * k)
        self.counters["frame_shape"] = (self.size, self.size)
        self.counters["frame_dtype"] = "float32"

    def check(self):
        """Per-frame summaries of a sample of the finished frames, drawn
        from the seed, against the reference's; every value must be
        equal, and a frame with no summary is missing."""
        rng = np.random.default_rng([self.seed, 1])
        done = [(i, got[i]) for ids, got in self.jobs for i in ids
                if i in got]
        k = min(int(self.traffic["check_frames"]), len(done))
        factor = float(self.config["filter_factor"])
        differing = 0
        for j in sorted(rng.choice(len(done), k, replace=False)):
            i, have = done[j]
            img = gen.render(i, self.size, self.config["recipe"])
            want = reference.summary(
                reference.diagram(img, gen.threshold(img, factor)))
            differing += summary_differing(have, want)
        return [Check("summary_values_differing", differing, 0),
                Check("frames_missing", self.failed, 0)]


def summary_differing(have: dict, want: dict) -> int:
    """Values of one frame's summary that differ from the reference's."""
    if have.get("overflow"):
        return 1 + 5 + 5 + 1
    n = int(have["count"] != want["count"])
    for key in ("top_births", "top_deaths"):
        a, b = have[key], want[key]
        n += sum(1 for x, y in zip(a, b) if x != y) + abs(len(a) - len(b))
    return n + int(have["persistence_sum"] != want["persistence_sum"])
