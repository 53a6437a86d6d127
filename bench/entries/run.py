"""Driver of ``PHEngine.run``: whole frames in a closed loop.

One caller hands the engine one host frame at a time and waits for its
diagram on the host before it sends the next, as a user processing a
survey frame by frame does.  The Variant-2 threshold is the engine's own
(``auto_threshold``, what ``run(frame)`` computes on the host).

Frames: ``traffic["frames"]`` distinct frames, their ids drawn from the
seed, are rendered in set-up while the programs load; frame ``i`` of the
window is base frame ``i % frames`` under the ``(i // frames) % 8``-th
flip or transpose, so no two calls of a window see the same pixels.  The
warm-up call gets a frame of its own.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import frames as gen
from bench import reference
from bench.harness import Cell, Check, Unit, engine_config


def dihedral(img: np.ndarray, k: int) -> np.ndarray:
    """The ``k``-th (0-7) of the square's flips and transposes."""
    out = img.T if k & 4 else img
    if k & 1:
        out = out[::-1]
    if k & 2:
        out = out[:, ::-1]
    return np.ascontiguousarray(out)


class Driver(Cell):

    def setup(self):
        import jax
        from repro.ph import PHEngine
        cfg, tr = self.config, self.traffic
        self.size = int(cfg["frame_edge"])
        self.engine = PHEngine(engine_config(cfg))
        rng = np.random.default_rng(self.seed)
        ids = rng.choice(2 ** 40, int(tr["frames"]) + 1, replace=False)
        self.warm_id, self.ids = int(ids[0]), [int(i) for i in ids[1:]]
        t = time.perf_counter()
        pool = ThreadPoolExecutor(int(tr["render_threads"]))
        try:
            futs = [pool.submit(gen.render, i, self.size, cfg["recipe"])
                    for i in [self.warm_id] + self.ids]
            warm = futs[0].result()
            self.setup_parts["first_frame_s"] = time.perf_counter() - t
            t = time.perf_counter()
            with jax.default_device(self.devices[0]):
                self._call(warm)
            self.setup_parts["warm_call_s"] = time.perf_counter() - t
            t = time.perf_counter()
            self.base = [f.result() for f in futs[1:]]
            self.setup_parts["frames_wait_s"] = time.perf_counter() - t
        finally:
            pool.shutdown(wait=True, cancel_futures=True)
        self.results: list = []

    def _call(self, frame):
        """One user call: threshold, run, diagram to the host."""
        import jax
        with self.span("threshold"):
            t = self.engine.auto_threshold(frame)
        with self.span("run"):
            res = self.engine.run(frame, t)
        with self.span("to_host"):
            diag = jax.device_get(res.diagram)
        return res, diag

    def frame(self, i: int) -> np.ndarray:
        k = len(self.base)
        return dihedral(self.base[i % k], (i // k) % 8)

    def window(self, seconds):
        import jax
        units = []
        t0 = time.perf_counter()
        i = 0
        with jax.default_device(self.devices[0]):
            while time.perf_counter() - t0 < seconds:
                frame = self.frame(i)
                self.attempted += 1
                res, diag = self._call(frame)
                units.append(Unit(frame.size / 1e6, time.perf_counter()))
                self.results.append((i, res.regrow.final_max_candidates,
                                     diag))
                i += 1
        return units

    def after_trace(self):
        # Candidates of the window's frames against the tier they ran at.
        import jax
        cand = cap = 0
        with jax.default_device(self.devices[0]):
            for i, mc, _ in self.results:
                cand += self.engine.num_candidates(self.frame(i))
                cap += mc
        self.counters.update(candidates=cand, candidate_capacity=cap)
        self.counters["frame_shape"] = (self.size, self.size)
        self.counters["frame_dtype"] = "float32"

    def check(self):
        """Full diagrams of a sample of the window's frames, drawn from the
        seed, against the reference: every row must be equal."""
        rng = np.random.default_rng([self.seed, 1])
        k = min(int(self.traffic["check_frames"]), len(self.results))
        pick = sorted(rng.choice(len(self.results), k, replace=False))
        factor = float(self.config["filter_factor"])
        differing = 0
        for j in pick:
            i, _, diag = self.results[j]
            img = self.frame(i)
            want = reference.diagram(img, gen.threshold(img, factor))
            differing += rows_differing(host_rows(diag), want)
        return [Check("rows_differing", differing, 0)]


def host_rows(diag) -> np.ndarray:
    """A host diagram as ``(C, 4)`` rows, overflowed ones included."""
    c = int(diag.count)
    rows = np.stack([np.asarray(diag.birth[:c], np.float64),
                     np.asarray(diag.death[:c], np.float64),
                     np.asarray(diag.p_birth[:c], np.float64),
                     np.asarray(diag.p_death[:c], np.float64)], 1)
    if bool(diag.overflow):
        rows = rows[:0]
    return rows


def rows_differing(got: np.ndarray, want: np.ndarray) -> int:
    """Rows that differ, a missing or extra row counting as one each."""
    n = min(len(got), len(want))
    same = np.all(got[:n] == want[:n], axis=1)
    return int(n - same.sum()) + abs(len(got) - len(want))
