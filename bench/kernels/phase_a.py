"""Phase-A kernel (``repro.kernels.ph_phase_a``): algorithmic bytes.

Per pixel the kernel needs the image once at its itemsize and writes two
int32 planes (the steepest-ascent pointer and the higher-neighbour
bitmask): 4 + 4 + 4 = 12 bytes per float32 pixel.  These are the
algorithm's bytes, not the three row-shifted input planes the current
implementation materialises, so fusing those away reads as a gain.  The
kernel does a handful of comparisons per byte, so HBM bandwidth bounds it.
"""
from __future__ import annotations

import numpy as np



def is_call(op: str) -> bool:
    """Whether a trace operation (``bench.trace.short_name``) is one call:
    the Pallas custom call the compiled program names after ``phase_a``."""
    return op.startswith("phase_a") and op.endswith("(custom-call)")


def bytes_moved(shape, dtype) -> int:
    """Least HBM bytes of one call on an ``(h, w)`` image of ``dtype``."""
    h, w = shape
    return h * w * (np.dtype(dtype).itemsize + 4 + 4)


def flops(shape, dtype) -> int:
    """No multiply-adds: comparisons only, far under the bandwidth bound."""
    return 0
