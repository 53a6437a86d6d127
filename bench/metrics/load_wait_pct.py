"""Share of the window's distributed jobs (``ph.job`` spans) that the
dispatch loop spent waiting for a staged round (``ph.load_wait``), as a
percentage: how far the host loader sets the job's pace."""
from bench import stages


def read(run):
    spans = stages.window_spans(run)
    waits = [s.seconds for s in spans if s.name == "ph.load_wait"]
    jobs = sum(s.seconds for s in spans if s.name == "ph.job")
    if not waits or not jobs:
        return None
    return 100.0 * sum(waits) / jobs
