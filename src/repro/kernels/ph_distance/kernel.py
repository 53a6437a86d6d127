"""Pallas kernel for the pairwise diagram-distance matrix.

The (B, B) pair grid streams per-diagram blocks through the pipeline:
each grid step (i, j) receives row i of the projection / diagonal /
profile tables through one set of BlockSpecs and row j through a second
set over the *same* device arrays (two ``in_specs`` per array, i- and
j-indexed — the pair-grid twin of the phase-C edge stream), sorts the
two augmented 2F-vectors per direction on-chip, and writes the two
scalar distances straight into their (i, j) output cells.  Relative to
the XLA reference — which materializes the full (B, B, K, 2F)
augmented/sorted tensor through vmap — the kernel's working set per
step is just the two diagrams' tables: 4·K·F lanes plus two profiles
(K = 16, F = 8192, f32: ~2 MiB of VMEM), independent of B.

Bit-identity with ``ref.distance_matrix`` holds by construction: the
kernel body calls :func:`ref.pair_distances` — the literal function the
reference vmaps — on identically prepared inputs, so there is no second
implementation to diverge (``tests/test_filtration_distance.py`` checks
equality bitwise anyway, in interpret mode).

Mosaic cannot compile this kernel: its blocks respect the (8, 128) rule,
but ``jnp.sort`` has no Pallas TPU lowering.  Pre-sorting each diagram's
projections in ``ref`` does not remove it, because the sorted vectors
augment one diagram with the other's diagonal projections, so they are
per pair.  ``repro.kernels.backend`` therefore names the XLA reference as
the TPU implementation (``NO_MOSAIC``), and this kernel runs only when a
caller asks for ``interpret=True``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import I32_ZERO
from repro.kernels.ph_distance import ref


def _dist_kernel(pts_a_ref, diag_a_ref, prof_a_ref,
                 pts_b_ref, diag_b_ref, prof_b_ref, sw_ref, bn_ref):
    sw, bn = ref.pair_distances(
        pts_a_ref[0], diag_a_ref[0], prof_a_ref[0, 0],
        pts_b_ref[0], diag_b_ref[0], prof_b_ref[0, 0])
    sw_ref[...] = jnp.full(sw_ref.shape, sw, sw_ref.dtype)
    bn_ref[...] = jnp.full(bn_ref.shape, bn, bn_ref.dtype)


def distance_matrix(pts, diag, prof, *, interpret: bool = False):
    """Blocked Pallas twin of ``ref.distance_matrix`` (same signature
    plus ``interpret``).  ``pts``/``diag`` are (B, K, F) projection
    tables, ``prof`` the (B, F) descending persistence profiles — all
    three from the shared preparation stages in ``ref``.

    Every block spans the full extent of its array's last two axes (the
    (8, 128) rule): profiles travel as (B, 1, F), and each pair writes
    its scalars into a lane row of a (B*B, 1, 128) table.
    """
    b, k, f = pts.shape
    prof3 = prof.reshape(b, 1, f)
    tbl_i = pl.BlockSpec((1, k, f), lambda i, j: (i, I32_ZERO, I32_ZERO))
    tbl_j = pl.BlockSpec((1, k, f), lambda i, j: (j, I32_ZERO, I32_ZERO))
    prof_i = pl.BlockSpec((1, 1, f), lambda i, j: (i, I32_ZERO, I32_ZERO))
    prof_j = pl.BlockSpec((1, 1, f), lambda i, j: (j, I32_ZERO, I32_ZERO))
    cell = pl.BlockSpec((1, 1, 128),
                        lambda i, j: (i * b + j, I32_ZERO, I32_ZERO))

    sw, bn = pl.pallas_call(
        _dist_kernel,
        grid=(b, b),
        in_specs=[tbl_i, tbl_i, prof_i, tbl_j, tbl_j, prof_j],
        out_specs=[cell, cell],
        out_shape=[jax.ShapeDtypeStruct((b * b, 1, 128), pts.dtype),
                   jax.ShapeDtypeStruct((b * b, 1, 128), prof.dtype)],
        interpret=interpret,
        name="distance",
    )(pts, diag, prof3, pts, diag, prof3)
    return sw[:, 0, 0].reshape(b, b), bn[:, 0, 0].reshape(b, b)
