"""The metrics read from the program's own spans and stage scopes
(``bench/stages.py``), on a hand-built trace with hand-built spans, and
the program's spans placed in a profile recorded on this host."""
import glob
import os
import shutil
import time
from pathlib import Path

import pytest

from bench import stages
from bench import trace as bench_trace
from bench.harness import Run, Unit, metric_reader
from bench.tests.test_trace import _plane, _profile

ROOT = Path(__file__).resolve().parents[2]
NEW = ("merge_busy_pct", "threshold_ms", "merge_fill_pct", "idle_host_pct")
# Host clock = trace clock - 1000 us (the start marker sits at trace 0).
MARK = -1_000_000


def _ns(us):
    """Host-clock ns of a trace-clock time in us."""
    return int(us * 1000) + MARK


class _Plan:
    def __init__(self, smap):
        self.smap = smap

    def stage_map(self):
        return self.smap


@pytest.fixture
def hand(monkeypatch):
    """One chip over a traced stretch of [0, 100] us:

    ops: while.1 [10, 50] and fusion.3 [20, 30] (merge), fusion.7
    [60, 70] (phase B), copy.9 [80, 90] (no stage), fusion.5 [90, 95]
    (merge in plan 1, diagram in plan 2: ambiguous).  Busy 65 us, merge
    40 us; idle [0, 10], [50, 60], [70, 80], [95, 100].

    program spans (trace-clock us): run 1 [-5, 52] (plan 1, 30 of 100
    candidates) with threshold [-5, 4], cast [4, 8], dispatch [8, 12],
    wait [12, 52]; run 2 [55, 99] (plan 2, 20 of 100) with threshold
    [55, 58], dispatch [58, 61], wait [61, 99]; a warm-up threshold
    before the window.  Idle under host work: [0, 10] + [55, 60] = 15
    us; under wait 16 us; under no span [52, 55] + [99, 100] = 4 us."""
    from repro.ph import trace
    text = (_plane(1, "/host:CPU", "main", [("bench.trace_start", 0, 0),
                                            ("bench.trace_stop", 100, 0)])
            + _plane(2, "/device:TPU:0", "XLA Ops",
                     [("while.1", 10, 40), ("fusion.3", 20, 10),
                      ("fusion.7", 60, 10), ("copy.9", 80, 10),
                      ("fusion.5", 90, 5)]))
    summary = bench_trace.Summary.from_profiles([_profile(text)], [0],
                                                marks=[MARK])
    spans = []

    def add(name, a, b, **attrs):
        spans.append(trace.Span(name, len(spans) + 1, 0, 1, _ns(a), _ns(b),
                                attrs))

    add("ph.threshold", -2000, -1900)            # before the window
    add("ph.threshold", -5, 4)
    add("ph.cast", 4, 8)
    add("ph.dispatch", 8, 12, plan=1)
    add("ph.wait", 12, 52)
    add("ph.run", -5, 52, plan=1, candidates=30, max_candidates=100)
    add("ph.threshold", 55, 58)
    add("ph.dispatch", 58, 61, plan=2)
    add("ph.wait", 61, 99)
    add("ph.run", 55, 99, plan=2, candidates=20, max_candidates=100)
    plans = {1: _Plan({"while.1": "ph.merge", "fusion.3": "ph.merge",
                       "fusion.5": "ph.merge", "fusion.7": "ph.phase_b"}),
             2: _Plan({"while.1": "ph.merge", "fusion.5": "ph.diagram"})}
    monkeypatch.setattr(trace, "spans", lambda since_ns=0: list(spans))
    monkeypatch.setattr(trace, "plan", plans.get)
    monkeypatch.setattr(stages, "_maps", {})
    return Run(ROOT, "TPU v5 lite", 1.0, t0=_ns(-10) / 1e9,
               units=[Unit(1.0, _ns(52.5) / 1e9), Unit(1.0, _ns(99.5) / 1e9)],
               trace=summary)


def _read(run, name):
    return metric_reader(ROOT, name).read(run)


def test_window_spans_and_stage_map(hand):
    got = stages.window_spans(hand)
    assert len(got) == 9 and got[0].start_ns == _ns(-5)
    # fusion.5 is mapped to two stages by the window's plans: left out.
    assert stages.stage_map(hand) == {"while.1": "ph.merge",
                                      "fusion.3": "ph.merge",
                                      "fusion.7": "ph.phase_b"}
    assert stages.instruction("%fusion.3 = f32[8]{0} fusion(%p)") == \
        "fusion.3"


def test_merge_busy_pct(hand):
    assert stages.stage_busy_s(hand) == {
        "busy": pytest.approx(65e-6), "ph.merge": pytest.approx(40e-6),
        "ph.phase_b": pytest.approx(10e-6)}
    assert _read(hand, "merge_busy_pct") == pytest.approx(100 * 40 / 65)


def test_threshold_ms(hand):
    assert _read(hand, "threshold_ms") == pytest.approx(6e-3)


def test_merge_fill_pct(hand):
    assert _read(hand, "merge_fill_pct") == pytest.approx(25.0)


def test_idle_host_pct(hand):
    idle = stages.idle_by_span(hand)
    assert idle == {"ph.threshold": pytest.approx(7e-6),
                    "ph.cast": pytest.approx(4e-6),
                    "ph.dispatch": pytest.approx(4e-6),
                    "ph.wait": pytest.approx(16e-6),
                    None: pytest.approx(4e-6)}
    assert _read(hand, "idle_host_pct") == pytest.approx(100 * 15 / 35)


def test_innermost_span_is_the_last_opened():
    from repro.ph import trace
    outer = trace.Span("outer", 1, 0, 1, 0, 100)
    inner = trace.Span("inner", 2, 1, 1, 20, 40)
    edges, labels = stages.innermost([outer, inner])
    assert edges.tolist() == [0, 20, 40, 100]
    assert labels == ["outer", "inner", "outer"]
    edges, labels = stages.innermost([])
    assert len(edges) == 0 and labels == []


def test_no_program_spans_leave_the_metrics_out(hand, monkeypatch):
    """A program without ``repro.ph.trace`` (one older than the recorder)
    yields nothing, and raises nothing."""
    monkeypatch.setattr(stages, "_program", lambda: None)
    for name in NEW:
        assert _read(hand, name) is None
    hand.trace = None
    for name in ("merge_busy_pct", "idle_host_pct"):
        assert _read(hand, name) is None


def test_run_span_lies_where_the_profile_puts_it():
    """A tiny ``PHEngine.run`` inside a profiler session on this host: its
    ``ph.run`` annotation on the host plane, moved by the start marker's
    offset, lies within 1 ms of the recorder's span on the host clock."""
    import jax
    from repro.data import astro
    from repro.ph import PHConfig, PHEngine, trace
    eng = PHEngine(PHConfig(max_features=512, max_candidates=512))
    img = astro.generate_image(1, 48)
    eng.run(img, 100.0)                           # compiled before tracing
    tracer = bench_trace.Tracer([[0, 30]])
    tracer.start()
    try:
        deadline = time.perf_counter() + 30
        while not tracer.marks and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert tracer.marks
        t0 = time.perf_counter_ns()
        eng.run(img, 100.0)
        tracer.stop()
        rec = [s for s in trace.spans(t0) if s.name == "ph.run"][-1]
        path = glob.glob(os.path.join(tracer.dir, "0", "**", "*.xplane.pb"),
                         recursive=True)[0]
        prof = jax.profiler.ProfileData.from_file(path)
        events = {}
        for plane in prof.planes:
            if plane.name != bench_trace.HOST_PLANE:
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (bench_trace.START, "ph.run"):
                        events[ev.name] = ev
        offset = events[bench_trace.START].start_ns - tracer.marks[0]
        got = events["ph.run"]
        assert abs(got.start_ns - offset - rec.start_ns) < 1e6
        assert abs(got.end_ns - offset - rec.end_ns) < 1e6
    finally:
        tracer.stop()
        shutil.rmtree(tracer.dir, ignore_errors=True)
