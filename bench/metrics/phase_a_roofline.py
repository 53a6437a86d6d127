"""Phase-A kernel's share of its HBM roofline (``bench/kernels/phase_a.py``)
over the traced frames."""
from bench.roofline import share


def read(run):
    return share(run, "phase_a")
