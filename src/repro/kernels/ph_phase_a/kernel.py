"""Pallas TPU kernel for the fused PixHomology phase A.

One VMEM pass per 8-row block computes both per-pixel artifacts of
phase A from the same resident planes (src/repro/ph/DESIGN.md §2):

  1. load three row-shifted planes of the (-inf)-padded image (the same
     halo trick as the maxpool kernel: BlockSpecs cannot express
     overlapping windows, so rows r-1 / r / r+1 arrive as separate
     BlockSpec-tiled inputs, double-buffered by the Pallas pipeline);
  2. reduce the 3x3 window to the steepest-ascent pointer with full
     (value, row, col) total-order tie-breaking, masking out-of-image
     lanes exactly (ref.py's fill index -1 can never win — unlike the
     maxpool kernel this holds even for images containing the fill value);
  3. emit the strictly-higher 8-neighbor bitmask (basin-candidate flags).

Everything in the body is elementwise over row-shifted planes, which is
what Mosaic lowers.  The in-strip snap (pointer doubling until every
pixel reaches its furthest in-strip ancestor, then one half-hop) is a
data-dependent 1D gather, which Mosaic refuses ("Only 2D gather is
supported"), so it runs after the kernel as XLA doubling — literally
``ref.strip_snap``, the code the reference runs — and the kernel path is
bit-identical to ``ref.phase_a`` by construction of the shared snap plus
the per-pixel parity of the sweep (tests/test_kernels_phase_a.py).

Dtypes narrower than 32 bits are widened to 32 bits before the kernel:
the widening is exact and order-preserving, so pointers and mask bits
are unchanged, and every plane then tiles (8, 128) like float32.  The
kernel compiles for TPU v5e at 4096 x 4096 (tests/test_tpu_compile.py).

VMEM working set: 3 value planes of (8, W+2) plus ~4 int32 (8, W)
temporaries — ~56 KB per block at W=1024; W up to ~32k columns fits
16 MB VMEM.  Rows are padded to a multiple of 8 with -inf; the wrapper
slices them off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.grid import NEIGHBOR_OFFSETS
from repro.kernels.backend import I32_ZERO
from repro.kernels.maxpool.kernel import _pad_rows, _row_shifted_planes
from repro.kernels.maxpool.ref import _neg_inf
from repro.kernels.ph_phase_a.ref import strip_snap
# Rows per grid step: one sublane tile, as the (8, 128) block rule asks.
_BLOCK_ROWS = 8


def _phase_a_kernel(r0_ref, r1_ref, r2_ref, hop_ref, mask_ref, *,
                    height: int, width: int):
    i = pl.program_id(0)
    s, w = _BLOCK_ROWS, width
    planes = (r0_ref[...], r1_ref[...], r2_ref[...])   # (S, W+2) each
    x = planes[1][:, 1:1 + w]                          # self values

    lr = jax.lax.broadcasted_iota(jnp.int32, (s, w), 0)  # row within block
    cc = jax.lax.broadcasted_iota(jnp.int32, (s, w), 1)  # column
    grow = i * jnp.int32(s) + lr                         # global row

    # --- 3x3 argmax under (value, row, col), out-of-image never wins ---
    best_v = x
    best_dr = jnp.ones((s, w), jnp.int32)   # plane index: 1 = self row
    best_dc = jnp.ones((s, w), jnp.int32)
    for dr in (0, 1, 2):
        for dc in (0, 1, 2):
            if (dr, dc) == (1, 1):
                continue
            v = planes[dr][:, dc:dc + w]
            inb = ((grow + (dr - 1) >= 0) & (grow + (dr - 1) < height)
                   & (cc + (dc - 1) >= 0) & (cc + (dc - 1) < w))
            key_gt = ((jnp.int32(dr) > best_dr)
                      | ((jnp.int32(dr) == best_dr)
                         & (jnp.int32(dc) > best_dc)))
            take = inb & ((v > best_v) | ((v == best_v) & key_gt))
            best_v = jnp.where(take, v, best_v)
            best_dr = jnp.where(take, jnp.int32(dr), best_dr)
            best_dc = jnp.where(take, jnp.int32(dc), best_dc)
    # Unsnapped steepest-ascent pointer in global flat coordinates.
    hop_ref[...] = (grow + best_dr - 1) * jnp.int32(w) + (cc + best_dc - 1)

    # --- strictly-higher 8-neighbor bitmask (basin-candidate flags) ---
    mask = jnp.zeros((s, w), jnp.int32)
    for j, (dr, dc) in enumerate(NEIGHBOR_OFFSETS):
        v = planes[dr + 1][:, dc + 1:dc + 1 + w]
        inb = ((grow + dr >= 0) & (grow + dr < height)
               & (cc + dc >= 0) & (cc + dc < w))
        higher = v > x
        if (dr, dc) > (0, 0):             # flat-index tie-break is static
            higher = higher | (v == x)
        mask = mask | jnp.where(inb & higher, jnp.int32(1 << j),
                                jnp.int32(0))
    mask_ref[...] = mask


def _widen(image: jnp.ndarray) -> jnp.ndarray:
    """Exact, order-preserving widening of < 32-bit dtypes to 32 bits."""
    dt = jnp.dtype(image.dtype)
    if dt.itemsize >= 4:
        return image
    return image.astype(jnp.float32 if dt.kind == "f" or dt == jnp.bfloat16
                        else jnp.int32)


@jax.named_scope("ph.phase_a")
def sweep(image: jnp.ndarray, *, interpret: bool = False):
    """The kernel alone: unsnapped pointers and mask, flat int32 — the
    Pallas twin of ``ref.pointer_and_mask_sweep``.  Every output pixel is
    independent of the blocking, so blocks are always ``_BLOCK_ROWS``
    rows (the sublane tile), whatever ``strip_rows`` the snap uses."""
    image = _widen(image)
    h, w = image.shape
    s = _BLOCK_ROWS
    hp = -(-h // s) * s                    # ceil to a block multiple
    fill = _neg_inf(image.dtype)

    r0, r1, r2 = _row_shifted_planes(image, fill)
    r0, r1, r2 = (_pad_rows(p, hp - h, fill) for p in (r0, r1, r2))

    kernel = functools.partial(_phase_a_kernel, height=h, width=w)
    in_spec = pl.BlockSpec((s, w + 2), lambda i: (i, I32_ZERO))
    out_spec = pl.BlockSpec((s, w), lambda i: (i, I32_ZERO))
    hop, mask = pl.pallas_call(
        kernel,
        grid=(hp // s,),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((hp, w), jnp.int32),
                   jax.ShapeDtypeStruct((hp, w), jnp.int32)],
        interpret=interpret,
        name="phase_a",
    )(r0, r1, r2)
    return hop[:h].reshape(-1), mask[:h].reshape(-1)


@functools.partial(jax.jit, static_argnames=("strip_rows", "interpret"))
def phase_a(image: jnp.ndarray, *, strip_rows: int = 8,
            interpret: bool = False):
    """Fused phase A; bit-identical to ``ref.phase_a`` (flat int32 pair)."""
    hop, mask = sweep(image, interpret=interpret)
    return strip_snap(hop, image.shape, strip_rows), mask
