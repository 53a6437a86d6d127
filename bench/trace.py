"""Profiler trace of stretches of a run's window, and its reduction to
numbers.

The traced run profiles the window in stretches, each a profiler session
of its own, at fixed offsets from the window's start (the traffic file's
``trace_stretches_s``): a chip's trace buffer holds a few million
operations, and the scan merge emits some hundred thousand a second, so
one session cannot hold a whole 4096² frame.  Spread over the window, the
stretches sample every stage of the closed loop.

Each session opens with a start marker and closes with a stop marker, host
events named ``bench.trace_start`` / ``bench.trace_stop``.  Device planes
carry one event per device operation.  The entry driver's spans
(threshold, run, diagram to host, job) are kept on the host clock and
placed in each stretch by its start marker.  From these:

* busy: the union of a chip's operation intervals inside the stretches;
  idle is the rest;
* kernel time: the summed durations of the operations a kernel's trace
  names match, counting only operations wholly inside a stretch;
* the breakdown: the operations that took most time over all chips, and
  the longest idle gaps, each named by the innermost driver span that
  covers most of it.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
import threading
import time

import numpy as np

START, STOP = "bench.trace_start", "bench.trace_stop"
DROPPED = "Trace Buffers Dropped"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


class Tracer:
    """Profiles ``stretches``, ``(start, end)`` seconds after the window
    opens, from a thread of its own while the driver runs; a stretch that
    has not begun when the window closes is skipped.  With no stretches
    it only names the driver's spans in the trace viewer."""

    def __init__(self, stretches=()):
        self.stretches = [(float(a), float(b)) for a, b in stretches]
        self.spans: list[tuple[str, int, int]] = []   # perf_counter_ns
        self.marks: list[int] = []      # perf_counter_ns at each start mark
        self.stats: dict = {}
        self.dir = None
        self._halt = threading.Event()
        self._thread = None

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        if self.stretches:
            self.spans.append((name, t, time.perf_counter_ns()))

    def start(self):
        """Opens the window: the stretches are timed from here."""
        if not self.stretches:
            return
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._t0 = time.perf_counter()
        self._thread = threading.Thread(target=self._follow, daemon=True)
        self._thread.start()

    def _follow(self):
        """A stretch that cannot start on time, because writing the last
        one took longer, starts late and keeps its length."""
        import jax
        done = self.stats["stretches"] = []   # [start, stop, stop_trace s]
        for k, (a, b) in enumerate(self.stretches):
            if self._halt.wait(max(0.0, self._t0 + a - time.perf_counter())):
                break
            jax.profiler.start_trace(os.path.join(self.dir, str(k)))
            mark = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(START):
                pass
            self.marks.append(mark)
            self._halt.wait(max(0.0, mark / 1e9 + b - a - time.perf_counter()))
            with jax.profiler.TraceAnnotation(STOP):
                pass
            t = time.perf_counter()
            jax.profiler.stop_trace()
            done.append([mark / 1e9 - self._t0, t - self._t0,
                         time.perf_counter() - t])

    def stop(self):
        """Closes the window: ends the stretch under way, skips the rest."""
        self._halt.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def summary(self, devices):
        self.stop()
        if self.dir is None:
            return None
        try:
            files = [sorted(glob.glob(os.path.join(
                self.dir, str(k), "**", "*.xplane.pb"), recursive=True))
                for k in range(len(self.marks))]
            if not all(files):
                return None
            t = time.perf_counter()
            out = Summary.from_files([f[0] for f in files],
                                     [d.id for d in devices],
                                     self.spans, self.marks)
            self.stats.update(
                trace_mb=sum(os.path.getsize(f[0]) for f in files) / 1e6,
                parse_s=time.perf_counter() - t)
            return out if out.window_s > 0 else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


_OPCODE = re.compile(r"\s([a-z][\w.-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...), ...`` -> ``fusion.3 (fusion)``:
    TPU traces name an operation by its whole HLO instruction."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = _OPCODE.search(rhs)
    return f"{lhs.lstrip('%')} ({m.group(1)})" if m else lhs.lstrip("%")


def union_length(iv: np.ndarray) -> float:
    """Length covered by the union of ``(start, end)`` intervals."""
    if len(iv) == 0:
        return 0.0
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    start = iv[:, 0]
    # A new block starts where an interval begins past all earlier ends.
    new = np.ones(len(iv), bool)
    new[1:] = start[1:] > reach[:-1]
    blocks = np.flatnonzero(new)
    ends = np.append(blocks[1:] - 1, len(iv) - 1)
    return float(np.sum(reach[ends] - start[blocks]))


def gaps(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """``(start, end)`` stretches of ``[lo, hi]`` no interval covers."""
    if len(iv) == 0:
        return np.array([[lo, hi]])
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    starts = np.concatenate([[lo], reach])
    ends = np.concatenate([iv[:, 0], [hi]])
    out = np.stack([starts, ends], 1)
    return out[out[:, 1] > out[:, 0]]


class Stretch:
    """One session: ``[lo, hi]`` in trace ns, the trace clock minus the
    host clock of the driver's spans (``offset``), and per chip its
    operations as ``(name ids, (k, 2) clipped ns intervals, wholly
    inside)``."""

    def __init__(self, lo, hi, offset, ops):
        self.lo, self.hi, self.offset, self.ops = lo, hi, offset, ops


class Summary:
    """The reduction of one traced run's stretches."""

    def __init__(self, names, stretches, spans=()):
        self.names = names              # operation name by id
        self.stretches = stretches
        self.spans = list(spans)        # (name, start_ns, end_ns), host clock

    @classmethod
    def from_files(cls, paths, device_ids, spans=(), marks=()):
        import jax
        profs = (jax.profiler.ProfileData.from_file(p) for p in paths)
        return cls.from_profiles(profs, device_ids, spans, marks)

    @classmethod
    def from_profiles(cls, profs, device_ids, spans=(), marks=(),
                      windows=None):
        """A stretch runs from its start marker to its stop marker (or
        over ``windows[k]``, in ns), cut where a chip's trace buffer
        overflowed first: past that point the trace holds no operations,
        which would read as idle.  ``marks[k]`` is the host clock of
        stretch ``k``'s start marker."""
        ids: dict[str, int] = {}
        wanted = {f"{DEVICE_PREFIX}{i}" for i in device_ids}
        out = []
        for k, prof in enumerate(profs):
            window = None if windows is None else windows[k]
            out.append(_read(prof, wanted, ids, window,
                             marks[k] if k < len(marks) else None))
        names = np.array(list(ids), object)
        return cls(names, out, spans)

    @property
    def window_s(self) -> float:
        return sum(s.hi - s.lo for s in self.stretches) / 1e9

    def busy_share(self) -> dict[str, float]:
        span = float(sum(s.hi - s.lo for s in self.stretches))
        busy: dict[str, float] = {}
        for s in self.stretches:
            for chip, (_, iv, _) in s.ops.items():
                busy[chip] = busy.get(chip, 0.0) + union_length(iv)
        return {chip: b / span for chip, b in sorted(busy.items())}

    @property
    def busy_s(self) -> float:
        """Seconds some operation ran, averaged over the chips."""
        shares = self.busy_share()
        return float(np.mean(list(shares.values()))) * self.window_s

    def _totals(self, whole_only: bool) -> tuple[np.ndarray, np.ndarray]:
        """Per operation name: calls and summed ns, over all chips."""
        n = np.zeros(len(self.names), np.int64)
        ns = np.zeros(len(self.names))
        for s in self.stretches:
            for ids, iv, whole in s.ops.values():
                keep = whole if whole_only else slice(None)
                n += np.bincount(ids[keep], minlength=len(n))
                ns += np.bincount(ids[keep], iv[keep, 1] - iv[keep, 0],
                                  minlength=len(n))
        return n, ns

    def op_time(self, match) -> tuple[int, float]:
        """Operations wholly inside a stretch whose short name ``match``
        accepts: their count and seconds, over all chips."""
        n, ns = self._totals(whole_only=True)
        pick = [i for i, name in enumerate(self.names)
                if match(short_name(name))]
        return int(n[pick].sum()), float(ns[pick].sum()) / 1e9

    def top_ops(self, k: int = 10) -> list:
        _, ns = self._totals(whole_only=False)
        best = np.argsort(-ns, kind="stable")[:k]
        return [[short_name(self.names[i]), float(ns[i]) / 1e9]
                for i in best if ns[i] > 0]

    def _host_doing(self, s: float, e: float) -> str:
        """The innermost driver span that covers most of ``[s, e]``, both
        on the host clock."""
        best, cover, best_len = "none", 0.0, float("inf")
        for name, a, b in self.spans:
            c = min(b, e) - max(a, s)
            if c > cover or (c == cover and c > 0 and b - a < best_len):
                best, cover, best_len = name, c, b - a
        return best

    def idle_gaps(self, k: int = 10) -> list:
        found = []
        for st in self.stretches:
            for chip, (_, iv, _) in sorted(st.ops.items()):
                for s, e in gaps(iv, st.lo, st.hi):
                    found.append((e - s, s - st.offset, e - st.offset, chip))
        found.sort(key=lambda g: -g[0])
        return [[f"{self._host_doing(s, e)}@{chip[len(DEVICE_PREFIX):]}",
                 d / 1e9] for d, s, e, chip in found[:k]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _read(prof, wanted, ids, window, mark) -> Stretch:
    marks, ops, dropped = {}, {}, []
    for plane in prof.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (START, STOP):
                        marks[ev.name] = ev.start_ns
        elif plane.name in wanted:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    dropped += [ev.start_ns for ev in line.events
                                if ev.name == DROPPED]
                    continue
                name_ids, iv = [], []
                for ev in line.events:
                    name_ids.append(ids.setdefault(ev.name, len(ids)))
                    iv.append((ev.start_ns, ev.end_ns))
                ops[plane.name] = (np.array(name_ids, np.int64),
                                   np.array(iv, float).reshape(-1, 2))
    if window is None:
        if len(marks) != 2:
            raise ValueError(f"the trace lacks its markers: {sorted(marks)}")
        window = (marks[START], marks[STOP])
    lo = window[0]
    hi = min([window[1]] + dropped)
    for chip, (name_ids, iv) in ops.items():
        keep = (iv[:, 1] > lo) & (iv[:, 0] < hi)
        iv = iv[keep]
        whole = (iv[:, 0] >= lo) & (iv[:, 1] <= hi)
        ops[chip] = (name_ids[keep], np.clip(iv, lo, hi), whole)
    for name in wanted - set(ops):
        ops[name] = (np.zeros(0, np.int64), np.zeros((0, 2)),
                     np.zeros(0, bool))
    offset = 0.0 if mark is None else marks.get(START, lo) - mark
    return Stretch(lo, hi, offset, ops)
