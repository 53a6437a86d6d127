"""Jit'd public wrapper for the fused phase-A stage with backend dispatch.

``use_pallas``/``interpret`` resolve through
:func:`repro.kernels.backend.resolve`: the compiled Pallas kernel on TPU,
the pure-XLA reference elsewhere, and the Pallas interpreter only when a
caller passes ``interpret=True`` (the tier-1 parity tests do).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import backend


def boundary_rows(h: int, strip_rows: int) -> np.ndarray:
    """Sorted first/last image rows of every ``strip_rows``-row strip.

    These rows are the **static frontier** of the strip decomposition: a
    strip-snapped pointer that is not a basin root always lands in one of
    them, so phase B's condensed label resolution only ever gathers over
    ``len(boundary_rows) * W`` entries instead of all ``H * W`` pixels.
    """
    s = max(1, min(strip_rows, h))
    rows = set()
    for r0 in range(0, h, s):
        rows.add(r0)
        rows.add(min(h, r0 + s) - 1)
    return np.asarray(sorted(rows), np.int32)


@functools.partial(jax.jit,
                   static_argnames=("strip_rows", "use_pallas", "interpret"))
def fused_phase_a(image: jnp.ndarray, *, strip_rows: int = 8,
                  use_pallas: bool | None = None, interpret: bool = False):
    """Fused phase A: ``(ptr, hi_mask)`` flat int32 arrays of ``image``.

    ``ptr`` is the strip-snapped steepest-ascent pointer (basin root or
    boundary-row pixel of an adjacent strip); ``hi_mask`` the
    strictly-higher 8-neighbor bitmask in ``NEIGHBOR_OFFSETS`` bit order.
    Both backends are bit-identical (tests/test_kernels_phase_a.py).
    """
    impl = backend.resolve("ph_phase_a", use_pallas, interpret)
    if impl != backend.XLA:
        from repro.kernels.ph_phase_a import kernel
        return kernel.phase_a(image, strip_rows=strip_rows,
                              interpret=impl == backend.INTERPRET)
    from repro.kernels.ph_phase_a import ref
    return ref.phase_a(image, strip_rows=strip_rows)
