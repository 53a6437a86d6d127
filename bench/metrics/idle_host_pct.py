"""Share of the chips' idle time in the traced stretches during which the
innermost open program span was host work the chip waits on
(``ph.threshold``, ``ph.cast``, ``ph.dispatch``, ``ph.regrow``), as a
percentage.  The rest is idle under ``ph.wait`` (gaps while the program
runs) or outside every span (``bench/stages.py``)."""
from bench import stages


def read(run):
    idle = stages.idle_by_span(run)
    if not idle or not stages.window_spans(run):
        return None
    total = sum(idle.values())
    if not total:
        return None
    return 100.0 * sum(idle.get(n, 0.0) for n in stages.HOST_WORK) / total
