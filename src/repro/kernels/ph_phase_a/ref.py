"""Pure-XLA reference for the fused phase-A stage (the Pallas oracle).

Phase A of the stage graph (src/repro/ph/DESIGN.md §2) turns an image into
the two per-pixel artifacts the rest of PixHomology consumes:

* ``ptr``  — the **strip-snapped steepest-ascent pointer**: each pixel's
  ascent chain is followed while it stays inside the pixel's row strip
  (``strip_rows`` consecutive image rows), then one extra half-hop is
  taken, so ``ptr[i]`` is either a basin root or a pixel in the *boundary
  row* of an adjacent strip.  This is the invariant the compacted-frontier
  label resolution (phase B) relies on: every pointer target outside the
  root set lives in a statically-known O(n / strip_rows) row subset.

* ``hi_mask`` — an int32 bitmask over :data:`NEIGHBOR_OFFSETS` (bit j set
  iff 8-neighbor j is inside the image and strictly higher under the
  (value, flat index) total order).  ``popcount >= 2`` is the
  basin-candidate flag: a pixel whose higher neighbors cannot span two
  basins can never be a death candidate, and the mask lets the exact
  candidate test (phase B) skip re-deriving rank comparisons.

The strip snap is exact, not approximate: its fixed point composed with
the frontier resolution reaches the same labels as whole-image pointer
doubling (tests/test_kernels_phase_a.py proves bit-equality), so fused and
pooled phase A are interchangeable stage implementations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.grid import NEIGHBOR_OFFSETS, fixed_point_iterate, shift2d
from repro.kernels.maxpool.ref import _neg_inf


@jax.named_scope("ph.phase_a")
def pointer_and_mask_sweep(image: jnp.ndarray
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One 8-offset sweep emitting (steepest pointer, higher bitmask).

    This is the XLA expression of the kernel's fused VMEM pass: each
    shifted neighbor plane is materialized once and feeds *both* the
    argmax reduction (identical to ``maxpool.ref.argmaxpool3x3``) and the
    strictly-higher mask bit, instead of two separate pooled sweeps.

    Mask bit j (:data:`NEIGHBOR_OFFSETS` order) is set iff neighbor j is
    inside the image and ``(v_nb, flat_nb) > (v, flat)``; within a 3x3
    window the flat order equals the (dr, dc) lexicographic order, so the
    index tie-break is static per offset.  Out-of-image neighbors never
    win the argmax nor count as higher (exact parity with the rank-based
    test, even for images containing the fill value).
    """
    h, w = image.shape
    rows = jnp.arange(h, dtype=jnp.int32)[:, None]
    cols = jnp.arange(w, dtype=jnp.int32)[None, :]
    flat = rows * w + cols
    fill = _neg_inf(image.dtype)

    best_v = image
    best_i = flat
    mask = jnp.zeros(image.shape, jnp.int32)
    for j, (dr, dc) in enumerate(NEIGHBOR_OFFSETS):
        v = shift2d(image, dr, dc, fill)
        i = shift2d(flat, dr, dc, jnp.int32(-1))
        better = (v > best_v) | ((v == best_v) & (i > best_i))
        best_v = jnp.where(better, v, best_v)
        best_i = jnp.where(better, i, best_i)
        higher = v > image
        if (dr, dc) > (0, 0):      # neighbor flat index > self on value ties
            higher = higher | (v == image)
        mask = mask | jnp.where((i >= 0) & higher, jnp.int32(1 << j),
                                jnp.int32(0))
    return best_i, mask


@jax.named_scope("ph.snap")
def strip_snap(hop: jnp.ndarray, shape: tuple[int, int], strip_rows: int,
               *, with_stats: bool = False):
    """Snap flat steepest-ascent pointers ``hop`` to each pixel's furthest
    in-strip ancestor, plus one half-hop out of the strip.

    Pointer doubling with escapes frozen; shared verbatim by this
    reference and the Pallas path (whose kernel emits ``hop``), so the
    two cannot diverge here.  ``with_stats`` also returns the doubling
    iteration count.
    """
    h, w = shape
    srows = max(1, min(strip_rows, h))
    span = w * srows                 # strip id of flat pixel g = g // span
    idx = jnp.arange(h * w, dtype=jnp.int32)
    esc = hop // span != idx // span                   # hop leaves the strip
    m0 = jnp.where(esc, idx, hop)                      # freeze escapes
    m, snap_iters = fixed_point_iterate(lambda q: q[q], m0)
    hm = hop[m]                                        # half-hop out
    ptr = jnp.where(hm // span != m // span, hm, m)
    return (ptr, snap_iters) if with_stats else ptr


@functools.partial(jax.jit, static_argnames=("strip_rows", "with_stats"))
def phase_a(image: jnp.ndarray, *, strip_rows: int = 8,
            with_stats: bool = False):
    """Fused phase A on the whole image: ``(ptr, hi_mask)`` flat int32.

    Semantics identical to the Pallas path: steepest-ascent pointers
    under the (value, flat index) total order, snapped to each pixel's
    furthest in-strip ancestor, plus one half-hop out of the strip; and
    the strictly-higher neighbor bitmask.  ``with_stats`` additionally
    returns the in-strip snap iteration count (benchmarks only).
    """
    hop2d, mask2d = pointer_and_mask_sweep(image)      # one fused sweep
    out = strip_snap(hop2d.reshape(-1), image.shape, strip_rows,
                     with_stats=with_stats)
    if with_stats:
        return out[0], mask2d.reshape(-1), out[1]
    return out, mask2d.reshape(-1)
