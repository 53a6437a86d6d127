"""Share of the chip's busy time in the traced stretches that the merge
stage (``ph.merge`` named scope) kept busy, as a percentage: the union of
its operations' intervals over the union of all operations' intervals,
summed over stretches and chips (``bench/stages.py``)."""
from bench import stages


def read(run):
    busy = stages.stage_busy_s(run)
    if not busy or not busy["busy"]:
        return None
    return 100.0 * busy.get("ph.merge", 0.0) / busy["busy"]
