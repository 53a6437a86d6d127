"""Pallas TPU kernel for fused 3x3 max / argmax pooling (paper's hot spot).

The paper's PixHomology spends its array time in ``maxpool2d`` /
``arg-maxpool2d`` (Algorithm 1 lines 1 and 6).  On TPU we fuse the two into a
single VMEM-resident pass and make the reduction *separable* (vertical then
horizontal), so each output tile does 4 comparisons/pixel instead of 8.

TPU adaptation (src/repro/ph/DESIGN.md §2): Pallas BlockSpecs cannot express overlapping
(haloed) windows, so the host wrapper materializes three row-shifted views of
the (-inf)-padded image (rows r-1, r, r+1).  The kernel then:

  1. loads the three (block_rows, W+2) row planes into VMEM (BlockSpec-tiled,
     double-buffered by the Pallas pipeline);
  2. reduces vertically with (value, row) tie-breaking;
  3. reduces horizontally across three static column shifts with full
     (value, row, col) total-order tie-breaking — identical to ref.py;
  4. emits the pooled value plane and the int32 flat-index argmax plane.

Cost: 3 HBM reads of the image instead of 1 (the shifted views) — the
separable VMEM reduction and the fusion of max+argmax into one pass more than
pay for it versus four independent XLA reduce_window calls (see
DESIGN.md §Perf).  Row-block tiling keeps the VMEM working set to
~6 * block_rows * W * 4 bytes; W up to ~64k columns fits comfortably in 16 MB
VMEM with block_rows=8.

Tie-breaking note: within a 3x3 window, flat index order == (row, col)
lexicographic order (rows differ by at most 1, cols by at most 1), so the
kernel's (value, row, col) key equals ref.py's (value, flat_index) key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import I32_ZERO
from repro.kernels.maxpool.ref import _neg_inf, _pos_inf

_LANES = 128


def _pad_rows(x: jnp.ndarray, rows: int, fill) -> jnp.ndarray:
    if rows == 0:
        return x
    return jnp.pad(x, ((0, rows), (0, 0)), constant_values=fill)


def _row_shifted_planes(x: jnp.ndarray, fill):
    """Three (H, W+2) planes holding rows r-1, r, r+1 of the padded image."""
    h, w = x.shape
    padded = jnp.pad(x, 1, constant_values=fill)  # (H+2, W+2)
    return padded[0:h, :], padded[1:h + 1, :], padded[2:h + 2, :]


def _maxarg_kernel(r0_ref, r1_ref, r2_ref, val_ref, arg_ref, *, width: int,
                   block_rows: int, want_arg: bool, minimum: bool):
    i = pl.program_id(0)
    planes = [r0_ref[...], r1_ref[...], r2_ref[...]]  # (TH, W+2) each

    def better(v, bv):
        return (v < bv) if minimum else (v > bv)

    # --- vertical reduction with (value, row) tie-break (larger row wins) ---
    best_v = planes[0]
    best_dr = jnp.zeros_like(planes[0], dtype=jnp.int32)
    for dr in (1, 2):
        v = planes[dr]
        take = better(v, best_v) | (v == best_v)  # larger dr wins ties
        best_v = jnp.where(take, v, best_v)
        best_dr = jnp.where(take, jnp.int32(dr), best_dr)

    # --- horizontal reduction with (value, row, col) tie-break ---
    out_v = best_v[:, 0:width]
    out_dr = best_dr[:, 0:width]
    out_dc = jnp.zeros((block_rows, width), jnp.int32)
    for dc in (1, 2):
        v = best_v[:, dc:dc + width]
        r = best_dr[:, dc:dc + width]
        take = (better(v, out_v)
                | ((v == out_v) & (r > out_dr))
                | ((v == out_v) & (r == out_dr)))  # larger dc wins ties
        out_v = jnp.where(take, v, out_v)
        out_dr = jnp.where(take, r, out_dr)
        out_dc = jnp.where(take, jnp.int32(dc), out_dc)

    val_ref[...] = out_v
    if want_arg:
        rows = (i * block_rows - 1
                + jax.lax.broadcasted_iota(jnp.int32, (block_rows, width), 0)
                + out_dr)
        cols = (jax.lax.broadcasted_iota(jnp.int32, (block_rows, width), 1)
                - 1 + out_dc)
        arg_ref[...] = rows * jnp.int32(width) + cols


def _pool_call(x: jnp.ndarray, *, want_arg: bool, minimum: bool,
               interpret: bool, block_rows: int):
    h, w = x.shape
    fill = _pos_inf(x.dtype) if minimum else _neg_inf(x.dtype)
    th = max(1, min(block_rows, h))
    hp = -(-h // th) * th  # ceil to a multiple of the row block

    r0, r1, r2 = _row_shifted_planes(x, fill)
    r0, r1, r2 = (_pad_rows(p, hp - h, fill) for p in (r0, r1, r2))

    kernel = functools.partial(_maxarg_kernel, width=w, block_rows=th,
                               want_arg=want_arg, minimum=minimum)
    in_spec = pl.BlockSpec((th, w + 2), lambda i: (i, I32_ZERO))
    out_spec = pl.BlockSpec((th, w), lambda i: (i, I32_ZERO))
    out_val, out_arg = pl.pallas_call(
        kernel,
        grid=(hp // th,),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((hp, w), x.dtype),
                   jax.ShapeDtypeStruct((hp, w), jnp.int32)],
        interpret=interpret,
        name="maxpool",
    )(r0, r1, r2)
    return out_val[:h], out_arg[:h]


def maxargmaxpool3x3(x: jnp.ndarray, *, interpret: bool = False,
                     block_rows: int = 8):
    """Fused (maxpool3x3, argmaxpool3x3); bit-identical to ref.py."""
    return _pool_call(x, want_arg=True, minimum=False, interpret=interpret,
                      block_rows=block_rows)


def maxpool3x3(x: jnp.ndarray, *, interpret: bool = False,
               block_rows: int = 8) -> jnp.ndarray:
    return _pool_call(x, want_arg=False, minimum=False, interpret=interpret,
                      block_rows=block_rows)[0]


def minpool3x3(x: jnp.ndarray, *, interpret: bool = False,
               block_rows: int = 8) -> jnp.ndarray:
    return _pool_call(x, want_arg=False, minimum=True, interpret=interpret,
                      block_rows=block_rows)[0]
