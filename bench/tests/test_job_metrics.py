"""The distributed job's metrics read from the program's spans
(``round_balance_pct``, ``load_wait_pct``) on hand-made spans."""
from pathlib import Path

import pytest

from bench import stages
from bench.harness import Run, Unit, metric_reader

ROOT = Path(__file__).resolve().parents[2]
NAMES = ("round_balance_pct", "load_wait_pct")


def _read(run, name):
    return metric_reader(ROOT, name).read(run)


@pytest.fixture
def job(monkeypatch):
    """Returns ``make(spans)``: a run whose window is [0, 1000] ns and
    whose program recorded ``spans``, ``(name, start, end, attrs)``."""
    from repro.ph import trace

    def make(spans):
        recs = [trace.Span(name, i + 1, 0, 1, a, b, dict(attrs))
                for i, (name, a, b, attrs) in enumerate(spans)]
        monkeypatch.setattr(trace, "spans", lambda since_ns=0: list(recs))
        return Run(ROOT, "TPU v5 lite", 1.0, t0=0.0,
                   units=[Unit(1.0, 1000 / 1e9)])

    return make


def _round(a, b, counts, chips=4):
    return ("ph.harvest", a, b, {"candidates": counts, "chips": chips})


def test_no_spans_read_nothing(job):
    run = job([])
    for name in NAMES:
        assert _read(run, name) is None
    # A program without the note or the wait span (one older than them):
    # its job and harvest spans alone read nothing either.
    run = job([("ph.job", 0, 900, {}), ("ph.harvest", 10, 20, {})])
    for name in NAMES:
        assert _read(run, name) is None


def test_even_round_reads_full_balance(job):
    run = job([("ph.job", 0, 900, {}),
               ("ph.load_wait", 0, 900, {}),
               _round(10, 20, [7, 7, 7, 7])])
    assert _read(run, "round_balance_pct") == pytest.approx(100.0)
    assert _read(run, "load_wait_pct") == pytest.approx(100.0)


def test_partial_round_and_waits(job):
    """Two full rounds then a last one of two frames over four chips;
    the job waited 150 of its 600 ns for staged rounds."""
    run = job([("ph.load_wait", 100, 200, {}),
               _round(200, 300, [10, 8, 6, 4]),
               ("ph.load_wait", 300, 330, {}),
               _round(330, 400, [5, 5, 5, 5]),
               ("ph.load_wait", 400, 420, {}),
               _round(420, 500, [3, 1]),
               ("ph.job", 100, 700, {}),
               # Before the window: the set-up's warm job.
               ("ph.job", -900, -100, {}),
               ("ph.load_wait", -900, -100, {}),
               _round(-300, -200, [1, 99])])
    last = stages.window_spans(run)[-1]
    assert last.name == "ph.job" and last.start_ns == 100
    want = 100 * (28 + 20 + 4) / (4 * 10 + 4 * 5 + 4 * 3)
    assert _read(run, "round_balance_pct") == pytest.approx(want)
    assert _read(run, "load_wait_pct") == pytest.approx(100 * 150 / 600)
    # A job of one part-filled round: two of four chips hold frames.
    alone = job([_round(0, 10, [3, 1])])
    assert _read(alone, "round_balance_pct") == pytest.approx(100 * 4 / 12)
