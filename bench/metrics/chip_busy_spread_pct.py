"""Busiest minus least busy chip's share of the traced stretch, in
percentage points: the straggler imbalance Variant-3 scheduling cuts."""


def read(run):
    if run.trace is None:
        return None
    shares = list(run.trace.busy_share().values())
    if len(shares) < 2:
        return None
    return 100.0 * (max(shares) - min(shares))
