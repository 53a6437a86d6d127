"""Candidates the merge swept (``candidates`` of the window's ``ph.run``
spans, carried out of the program with the overflow flag) over the
``max_candidates`` tier each call finished at, as a percentage: the
useful share of the capacity-sized merge sweep."""
from bench import stages


def read(run):
    runs = [s.attrs for s in stages.window_spans(run)
            if s.name == "ph.run" and "candidates" in s.attrs]
    cap = sum(a["max_candidates"] for a in runs)
    return 100.0 * sum(a["candidates"] for a in runs) / cap if cap else None
