"""Median duration of the window's ``ph.threshold`` program spans, in
milliseconds: the host's Variant-2 threshold statistic for one frame."""
import statistics

from bench import stages


def read(run):
    got = [s.seconds for s in stages.window_spans(run)
           if s.name == "ph.threshold"]
    return 1e3 * statistics.median(got) if got else None
