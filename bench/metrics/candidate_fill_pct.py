"""Death-point candidates (``PHEngine.num_candidates``) of the traced
frames over the ``max_candidates`` of the tier each ran at, as a
percentage: the useful share of the scan merge's fixed-length sweep."""


def read(run):
    cap = run.counters.get("candidate_capacity")
    if not cap:
        return None
    return 100.0 * run.counters["candidates"] / cap
