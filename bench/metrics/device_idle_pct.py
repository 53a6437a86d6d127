"""Share of the traced stretch in which no operation ran on a chip, as a
percentage, averaged over the cell's chips (profiler trace)."""


def read(run):
    if run.trace is None:
        return None
    shares = run.trace.busy_share()
    return 100.0 * (1.0 - sum(shares.values()) / len(shares))
